"""The reader of ``pack_class_share``: the program's ``pack_class_solves``
counter over the window's rounds, 0 on a program without it, and the
metric's entry in the benchmark."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

CELLS = ["shockwave-512x4.saturated", "shockwave-512x4-a100v100.saturated-typed"]


def _record(*stats):
    return {"setup_s": 1.0, "window_s": 10.0, "trace": None,
            "rounds": [{"decide_s": 1.0, "round_s": 1.2, "timings": {}, "stats": s,
                        "degrade": "none", "active": 10} for s in stats]}


def test_rounds_that_carry_the_counter_read_their_share():
    read = harness.metric_reader("pack_class_share")
    assert read(_record({"pack_class_solves": 1}, {"pack_class_solves": 1})) == 1.0
    assert read(_record({"pack_class_solves": 1}, {"solves": 1},
                        {"pack_class_solves": 1}, {})) == pytest.approx(0.5)


def test_a_record_without_the_counter_reads_zero():
    """The parent program: packing through the engine, no such counter."""
    read = harness.metric_reader("pack_class_share")
    assert read(_record({"solves": 1, "cold_instances": 1}, {})) == 0.0


def test_the_plain_cell_reports_the_share():
    (m,) = [m for m in harness.cell(CELLS[0])["per_layer"] if m["name"] == "pack_class_share"]
    assert m["moves"] == "decide_s" and m["source"] == "program_counter"
    pack_s = [m for m in harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]
              if m["name"] == "pack_s"]
    assert m["layer"] == pack_s[0]["layer"]


@pytest.mark.parametrize("name", CELLS)
def test_every_window_round_packs_on_classes_at_a_small_size(name):
    """16 nodes on the CPU: each window round solves its packing once on job
    classes, and the run stays correct against the reference."""
    import time

    spec = harness.cell(name)
    cluster = spec["config"]["cluster"]
    cluster["num_nodes"] = 16
    if "node_gpu_types" in cluster:
        cluster.update(node_gpu_types=[["a100", 8], ["v100", 8]], nodes_per_rack=8)
    spec["traffic"]["warmup_rounds"] = 2
    res = harness.run(spec, 2**31 + 181, 0.5, False, time.perf_counter(),
                      {"platform": "cpu", "kind": "cpu", "count": 1})
    rounds = res.pop("_rounds")
    assert res["correct"] is True, res["checks"]
    assert rounds and all(r.stats.get("pack_class_solves") == 1 for r in rounds)
    record = {"rounds": [{"stats": r.stats} for r in rounds]}
    assert harness.metric_reader("pack_class_share")(record) == 1.0
