"""Share of the window's rounds whose packing was solved on job classes,
with no job-level LAP (the program's ``pack_class_solves`` counter, once a
round); 0 where no round carries the counter."""


def read(record):
    rounds = record["rounds"]
    if not rounds:
        return None
    return sum(r["stats"].get("pack_class_solves", 0) for r in rounds) / len(rounds)
