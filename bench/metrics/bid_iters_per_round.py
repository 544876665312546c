"""Bid iterations of the fused migrate program per window round (the
program's ``fused_bid_iters`` counter: pair auctions plus the node match)."""


def read(record):
    rounds = record["rounds"]
    if not rounds or not any("fused_bid_iters" in r["stats"] for r in rounds):
        return None
    return sum(r["stats"].get("fused_bid_iters", 0) for r in rounds) / len(rounds)
