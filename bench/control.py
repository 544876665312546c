"""Readings that the limits of ``harness.LIMITS`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process: the cell's set-up and a short window of the
program, its numbers compared (the lower readings), and the same numbers of
the control on the same rounds: the reference computed in bfloat16, put in
the relabelling's place (the upper readings).  The benchmark's own runs do
not run this.  Prints one JSON line per seed and a summary line last.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness, reference  # noqa: E402


def control_readings(rounds, gangs, types=None, racks=None):
    """The control's numbers on the window's rounds, on a cluster with GPU
    types or racks under the reference's type and rack rules."""
    cost_gap = plan_gap = 0.0
    invalid = 0
    for r in rounds:
        mig = r.migration
        opt, _, _ = reference.relabel(mig.prev, mig.logical, gangs, types=types, racks=racks)
        cost, phys, node_map = reference.relabel(mig.prev, mig.logical, gangs, "bfloat16",
                                                 types=types, racks=racks)
        cost_gap = max(cost_gap, abs(cost - opt))
        exact = reference.plan_cost(mig.prev, phys, gangs, mig.logical,
                                    node_map=node_map, racks=racks)
        plan_gap = max(plan_gap, abs(exact - opt))
        invalid += bool(reference.plan_problems(phys, r.active, gangs, mig.logical,
                                                types=types, node_map=node_map))
    return {"cost_gap": cost_gap, "plan_cost_gap": plan_gap, "invalid_plans": invalid}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = harness.cell(args.workload)
    harness.enable_cache()
    try:
        device = harness.require_chip(spec["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    lower = {k: 0.0 for k in harness.LIMITS}
    upper = {k: float("inf") for k in harness.LIMITS}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(spec, seed, args.seconds, False, time.perf_counter(), device)
        program = {k: c["value"] for k, c in res["checks"].items()}
        control = control_readings(res["_rounds"], res["_gangs"],
                                   *harness.layout(spec["config"]["cluster"]))
        for k in harness.LIMITS:
            lower[k] = max(lower[k], program[k])
            upper[k] = min(upper[k], control[k])
        print(json.dumps({"seed": seed, "rounds": res["attempted"], "failed": res["failed"],
                          "program": program, "control": control}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower, "upper": upper,
                      "limits": harness.LIMITS, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
