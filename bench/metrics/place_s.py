"""Mean seconds per window round of the scheduler's ``place_s`` stage, as the
program times it (``RoundDecision.timings``)."""


def read(record):
    values = [r["timings"]["place_s"] for r in record["rounds"] if "place_s" in r["timings"]]
    return sum(values) / len(values) if values else None
