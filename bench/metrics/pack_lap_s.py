"""Seconds per window round of packing's LAP solve (the program's
``lap.solve`` span under ``pack``, its ``lap.prologue`` included)."""


def read(record):
    values = [sum(v for p, v in r["spans"].items() if "/pack/lap.solve" in "/" + p)
              for r in record["rounds"] if "spans" in r]
    return sum(values) / len(values) if values else None
