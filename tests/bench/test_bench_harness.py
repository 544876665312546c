"""The benchmark harness on the CPU: its refusals, its files, its traffic,
and its comparison, which has to pass the program and fail its control and
every fault planted in the timed path."""

import copy
import io
import json
import os
import re
import shutil
import subprocess
import sys
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, reference, traffic  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def small(name, nodes):
    """The cell as BENCHMARK.json gives it, on ``nodes`` nodes."""
    spec = harness.cell(name)
    spec["config"]["cluster"]["num_nodes"] = nodes
    return spec


def _run(spec, seed=2**31 + 3, seconds=0.5):
    res = harness.run(spec, seed, seconds, False, time.perf_counter(), FAKE_DEVICE)
    res.pop("_gangs")
    return res, res.pop("_rounds")


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    for rel in ["BENCHMARK.json"] + BENCHMARK["paths"]:
        src = os.path.join(ROOT, rel)
        if os.path.isdir(src):
            shutil.copytree(src, tmp_path / rel, ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, tmp_path / rel)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert "{" not in out.stdout


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    spec = harness.cell(name)
    assert spec["config"]["name"] == next(
        w["config"] for w in BENCHMARK["workloads"] if w["name"] == name)
    assert spec["end_to_end"] and spec["per_layer"]
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for m in spec["per_layer"]:
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def test_names_units_and_keys_follow_the_contract():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in BENCHMARK["paths"]))
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m["workloads"]) <= set(CELLS) if "workloads" in m else True
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.mark.parametrize("name", CELLS)
def test_the_trace_follows_the_seed_and_the_traffic_file(name):
    spec = harness.cell(name)
    cfg, mix = spec["config"], spec["traffic"]
    a = traffic.job_rows(cfg, mix, 2**31 + 11)
    b = traffic.job_rows(cfg, mix, 2**31 + 11)
    c = traffic.job_rows(cfg, mix, 12)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["num_gpus"], c["num_gpus"])
    # another seed offers the same work, the stream in another order
    assert np.array_equal(np.sort(a["num_gpus"]), np.sort(c["num_gpus"]))
    assert np.array_equal(a["num_gpus"][a["backlog"]], c["num_gpus"][c["backlog"]])
    assert traffic.offered_load(cfg, a) == pytest.approx(mix["offered_load"], rel=0.03)
    gpus = cfg["cluster"]["num_nodes"] * cfg["cluster"]["gpus_per_node"]
    backlog = a["backlog"]
    assert backlog.sum() == mix["backlog_per_gpu"] * gpus
    assert a["arrival_s"][backlog].max() < mix["backlog_window_s"]
    want = np.asarray(cfg["jobs"]["gangs"]["probs"])
    got = np.array([(a["num_gpus"] == s).mean() for s in cfg["jobs"]["gangs"]["sizes"]])
    assert got == pytest.approx(want / want.sum(), abs=1e-3)


def test_a_small_window_prints_the_result_line(monkeypatch):
    spec = small(CELLS[0], 16)
    monkeypatch.setattr(harness, "cell", lambda name: spec)
    monkeypatch.setattr(harness, "require_chip", lambda chips: dict(FAKE_DEVICE))
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", CELLS[0], "--seed", str(2**31 + 5),
                           "--seconds", "0.5", "--trace", "0"], time.perf_counter())
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result) == RESULT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    assert set(result["checks"]) == set(harness.LIMITS)


def _fused_plan_patched(monkeypatch, change):
    """Route every fused relabelling through ``change(prev, logical, gangs, res)``."""
    from repro.core.fused import FusedMigrationPlanner

    plan = FusedMigrationPlanner.plan

    def patched(self, prev, new_logical, num_gpus_of, **kw):
        return change(prev, new_logical, num_gpus_of,
                      plan(self, prev, new_logical, num_gpus_of, **kw))

    monkeypatch.setattr(FusedMigrationPlanner, "plan", patched)


def _result(prev, plan_slots, cost):
    from repro.core.cluster import PlacementPlan, count_migrations
    from repro.core.migration import MigrationResult

    phys = PlacementPlan(prev.cluster, plan_slots)
    return MigrationResult(phys, count_migrations(prev, phys), cost, None, 0.0, "planted")


def test_the_lower_precision_control_is_not_correct(monkeypatch):
    """The reference computed in bfloat16, put in the program's place.  At 16
    nodes every sum fits bfloat16's 8 bits and the control is exact, so the
    test runs where sums do not: 128 nodes."""

    def control(prev, logical, gangs, _res):
        cost, phys = reference.relabel(prev.slots, logical.slots, gangs, "bfloat16")
        return _result(prev, phys, cost)

    _fused_plan_patched(monkeypatch, control)
    res, _ = _run(small(CELLS[0], 128), seconds=4.0)
    assert res["correct"] is False
    assert res["checks"]["cost_gap"]["value"] > 0.0


def _cost_off_by_one_unit(prev, logical, gangs, res):
    res.matching_cost += 1.0 / 64
    return res


def _two_gpus_swapped(prev, logical, gangs, res):
    slots = res.physical_plan.slots.copy()
    busy = np.argwhere((slots != -1).any(-1))
    (n0, g0), (n1, g1) = busy[0], next(b for b in busy if b[0] != busy[0][0])
    slots[[n0, n1], [g0, g1]] = slots[[n1, n0], [g1, g0]]
    return _result(prev, slots, res.matching_cost)


def _previous_plan_returned(prev, logical, gangs, res):
    return _result(prev, prev.slots.copy(), res.matching_cost)


@pytest.mark.parametrize(
    "fault", [_cost_off_by_one_unit, _two_gpus_swapped, _previous_plan_returned],
    ids=["answer-altered-cost", "answer-altered-plan", "state-unchanged"],
)
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    _fused_plan_patched(monkeypatch, fault)
    res, _ = _run(small(CELLS[0], 16))
    assert res["correct"] is False


@pytest.mark.parametrize("config", ["shockwave-512x4", "helios-saturn-262x8"])
def test_the_program_is_correct_at_a_small_size(config):
    """Every configuration under bench/configs, the one not yet a cell too."""
    spec = dict(small(CELLS[0], 16), name=config + ".saturated")
    spec["config"] = harness.load_json(os.path.join(ROOT, "bench", "configs", config + ".json"))
    spec["config"]["cluster"]["num_nodes"] = 16
    res, rounds = _run(spec)
    assert res["correct"] is True, res["checks"]
    assert all(r.migration is not None for r in rounds)


@pytest.mark.parametrize("kc,kl", [(6, 4), (5, 8)])
def test_the_reference_agrees_with_the_programs_host_planner(kc, kl):
    from repro.core.cluster import ClusterSpec, PlacementPlan
    from repro.core.migration import plan_migration

    rng = np.random.default_rng(kc * kl)
    ids = np.arange(30)
    gangs = {int(j): int(rng.choice([1, 2, 4, 8])) for j in ids}

    def random_plan():
        slots = np.full((kc, kl, 2), -1, np.int64)
        for n in range(kc):
            for g in range(kl):
                k = int(rng.integers(0, 3))
                slots[n, g, :k] = rng.choice(ids, size=k, replace=False)
        return slots

    cluster = ClusterSpec(kc, kl)
    for _ in range(4):
        a, b = random_plan(), random_plan()
        want = plan_migration(PlacementPlan(cluster, a.copy()), PlacementPlan(cluster, b.copy()),
                              gangs, algorithm="node", backend="scipy")
        cost, phys = reference.relabel(a, b, gangs)
        assert cost == want.matching_cost
        assert reference.plan_cost(a, phys, gangs, b) == cost
        assert reference.plan_cost(a, want.physical_plan.slots, gangs, b) == cost


def test_plan_problems_names_each_broken_rule():
    gangs = {0: 1, 1: 2, 2: 8}
    slots = np.full((3, 4, 2), -1, np.int64)
    slots[0, 0, 0] = 0
    slots[1, :2, 0] = 1
    slots[1:3, :, 1] = 2
    assert reference.plan_problems(slots, np.array([0, 1, 2]), gangs) == []
    bad = copy.deepcopy(slots)
    bad[0, 1, 0] = 1  # job 1 on three GPUs over two nodes
    assert len(reference.plan_problems(bad, np.array([0, 1, 2]), gangs)) == 2
    assert reference.plan_problems(slots, np.array([0, 1]), gangs)  # job 2 not active
