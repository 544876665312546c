"""Host seconds of the fused migrate stage per window round: the program's
``migrate.fused`` span less its ``migrate.fused.program`` child (which ends
when the device has finished), i.e. preparing the inputs, the readout's
transfer and building the plan."""


def read(record):
    values = []
    for r in record["rounds"]:
        spans = r.get("spans", {})
        fused = [p for p in spans if "migrate.fused" in p.split("/")]
        if fused:
            values.append(sum(spans[p] for p in fused
                              if "migrate.fused.program" not in p.split("/")))
    return sum(values) / len(values) if values else None
