"""The A100/V100 cell's configuration, traffic and per-layer metrics: the
three readers of the program's typed counters, on hand-built records and on
a record from a program without them, and the cell itself at a small size
on the CPU."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

CELL = "shockwave-512x4-a100v100.saturated-typed"
METRICS = ["typed_match_share", "typed_node_match_iters", "node_price_top_eps"]
FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _record(*stats):
    return {"setup_s": 1.0, "window_s": 10.0, "trace": None,
            "rounds": [{"decide_s": 1.0, "round_s": 1.2, "timings": {}, "stats": s,
                        "degrade": "none", "active": 10} for s in stats]}


# a converged typed round, a typed round that fell back to the host
# planner (its counters kept), and a round served by the host planner alone
CONVERGED = {"fused_typed_rounds": 1, "fused_node_iters": 3000, "fused_node_price_top": 90000}
FELL_BACK = {"fused_host_fallbacks": 1, "fused_node_iters": 20000,
             "fused_node_price_top": 19000000}
HOST_ONLY = {"fused_host_fallbacks": 1}


@pytest.mark.parametrize("name,want", [
    ("typed_match_share", 1 / 3),
    ("typed_node_match_iters", 23000 / 3),
    ("node_price_top_eps", (90000 + 19000000) / 2),
])
def test_each_typed_reader_on_a_hand_built_record(name, want):
    assert harness.metric_reader(name)(_record(CONVERGED, FELL_BACK, HOST_ONLY)) == pytest.approx(want)


@pytest.mark.parametrize("name", METRICS)
def test_each_typed_reader_reads_nothing_where_the_program_records_nothing(name):
    """The parent program on this cell: every round falls back to the host
    planner and keeps no counter."""
    assert harness.metric_reader(name)(_record(HOST_ONLY, HOST_ONLY)) is None
    assert harness.metric_reader(name)(_record()) is None


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    spec = harness.cell(CELL)
    assert spec["chips"] == 1
    assert [m["name"] for m in spec["per_layer"]] == METRICS
    assert {m["moves"] for m in spec["per_layer"]} == {"decide_s"}
    cluster = spec["config"]["cluster"]
    types, racks = harness.layout(cluster)
    assert (types == "a100").sum() == (types == "v100").sum() == 256
    assert racks.max() == 31
    assert spec["config"]["reduced"] == []


def test_the_cell_runs_type_feasible_at_a_small_size():
    """32 nodes, 16 A100 and 16 V100 in two racks: every window round is
    served by the fused program's type-feasible node match, correct against
    the reference, and every per-layer metric reads."""
    spec = harness.cell(CELL)
    cluster = spec["config"]["cluster"]
    cluster.update(num_nodes=32, node_gpu_types=[["a100", 16], ["v100", 16]])
    spec["traffic"]["warmup_rounds"] = 2
    res = harness.run(spec, 2**31 + 21, 0.5, False, time.perf_counter(), FAKE_DEVICE)
    rounds = res.pop("_rounds")
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and rounds
    record = {"rounds": [{"stats": r.stats} for r in rounds]}
    assert harness.metric_reader("typed_match_share")(record) == 1.0
    assert harness.metric_reader("typed_node_match_iters")(record) > 0
    assert 0 < harness.metric_reader("node_price_top_eps")(record) < 2**24
