"""Mean seconds per window round of the scheduler's ``pack_s`` stage, as the
program times it (``RoundDecision.timings``)."""


def read(record):
    values = [r["timings"]["pack_s"] for r in record["rounds"] if "pack_s" in r["timings"]]
    return sum(values) / len(values) if values else None
