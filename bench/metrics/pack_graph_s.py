"""Seconds per window round that packing spends building its graph (the
program's ``pack.graph`` span)."""


def read(record):
    values = [sum(v for p, v in r["spans"].items() if p.endswith("pack/pack.graph"))
              for r in record["rounds"] if "spans" in r]
    return sum(values) / len(values) if values else None
