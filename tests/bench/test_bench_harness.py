"""The benchmark harness on the CPU: its refusals, its files, its traffic,
and its comparison, which has to pass the program and fail its control and
every fault planted in the timed path."""

import copy
import io
import itertools
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


def _result(prev, plan_slots, cost, node_map=None):
    from repro.core.cluster import PlacementPlan, count_migrations
    from repro.core.migration import MigrationResult

    phys = PlacementPlan(prev.cluster, plan_slots)
    return MigrationResult(phys, count_migrations(prev, phys), cost, node_map, 0.0, "planted")


def test_the_lower_precision_control_is_not_correct(monkeypatch):
    """The reference computed in bfloat16, put in the program's place.  At 16
    nodes every sum fits bfloat16's 8 bits and the control is exact, so the
    test runs where sums do not: 128 nodes."""

    def control(prev, logical, gangs, _res):
        cost, phys, _ = reference.relabel(prev.slots, logical.slots, gangs, "bfloat16")
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
        cost, phys, _ = reference.relabel(a, b, gangs)
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


# --------------------------------------------------------------------------- #
# a cluster with GPU types and racks
# --------------------------------------------------------------------------- #
def typed(nodes=16, per_rack=4):
    """The cell on ``nodes`` nodes, half A100 and half V100, in racks."""
    spec = small(CELLS[0], nodes)
    spec["config"]["cluster"].update(
        node_gpu_types=[["a100", nodes // 2], ["v100", nodes - nodes // 2]],
        nodes_per_rack=per_rack)
    return spec


def _valid_plan(rng, kc, kl, gangs):
    """Every job on ``gang`` GPUs of one node, in one pack slot; new ids.
    Gangs are powers of two, so that every cost is exact in float64."""
    slots = np.full((kc, kl, 2), -1, np.int64)
    for n, p in itertools.product(range(kc), range(2)):
        u = 0
        while u < kl:
            if rng.random() < 0.3:
                u += 1
                continue
            g = int(rng.choice([g for g in (1, 2, 4) if g <= kl - u]))
            j = len(gangs)
            gangs[j] = g
            slots[n, u:u + g, p] = j
            u += g
    return slots


def _instance(kc, kl, seed, crossing):
    """A previous plan and a logical one: the previous plan with its nodes
    and GPUs shuffled and some nodes laid out anew; with ``crossing``, only
    the first node and the last (of another type) swapped."""
    rng = np.random.default_rng(seed)
    gangs = {}
    prev = _valid_plan(rng, kc, kl, gangs)
    if crossing:
        logical = prev[[kc - 1] + list(range(1, kc - 1)) + [0]].copy()
    else:
        logical = prev[rng.permutation(kc)][:, rng.permutation(kl)].copy()
        fresh = rng.choice(kc, size=2, replace=False)
        logical[fresh] = _valid_plan(rng, 2, kl, gangs)
    return prev, logical, gangs


def _brute_force(prev, logical, gangs, types, racks):
    """The least relabelling cost over every node permutation and every GPU
    permutation of each node pair, from the reference's definitions."""
    kc, kl = prev.shape[:2]
    keep = set(prev[prev >= 0].tolist()) & set(logical[logical >= 0].tolist())

    def gpu(a, b):
        return sum(0.5 / gangs[j] for j in ({*a} ^ {*b}) & keep)

    pair = {(i, lo): min(sum(gpu(prev[i, u], logical[lo, v[u]]) for u in range(kl))
                         for v in itertools.permutations(range(kl)))
            for i in range(kc) for lo in range(kc)}
    best = np.inf
    for host in itertools.permutations(range(kc)):  # host[l]: physical node
        if any(types[host[lo]] != types[lo] for lo in range(kc)):
            continue
        best = min(best, sum(pair[host[lo], lo] + 0.5 * (racks[host[lo]] != racks[lo])
                             for lo in range(kc)))
    return best


TYPED_INSTANCES = {
    "5x2-two-types": (5, 2, ["a100"] * 3 + ["v100"] * 2, 2, 21, False),
    "4x4-two-types": (4, 4, ["a100", "a100", "v100", "v100"], 2, 22, False),
    "6x2-three-types": (6, 2, ["a100", "a100", "v100", "v100", "tpu-v5e", "tpu-v5e"], 3, 23, False),
    "4x4-untyped-optimum-crosses": (4, 4, ["a100", "a100", "v100", "v100"], 2, 24, True),
}


@pytest.mark.parametrize("case", TYPED_INSTANCES, ids=list(TYPED_INSTANCES))
def test_the_typed_racked_reference_is_the_brute_force_optimum(case):
    kc, kl, types, per_rack, seed, crossing = TYPED_INSTANCES[case]
    types, racks = np.array(types), np.arange(kc) // per_rack
    prev, logical, gangs = _instance(kc, kl, seed, crossing)
    cost, phys, node_map = reference.relabel(prev, logical, gangs, types=types, racks=racks)
    assert cost == _brute_force(prev, logical, gangs, types, racks)
    assert reference.plan_cost(prev, phys, gangs, logical, node_map=node_map, racks=racks) == cost
    active = np.array(list(gangs))
    assert reference.plan_problems(phys, active, gangs, logical, types=types,
                                   node_map=node_map) == []
    if crossing:
        untyped, _, untyped_map = reference.relabel(prev, logical, gangs, racks=racks)
        assert untyped < cost
        assert (types[untyped_map] != types).any()


@pytest.mark.parametrize("precision", ["float64", "bfloat16"])
@pytest.mark.parametrize("case", TYPED_INSTANCES, ids=list(TYPED_INSTANCES))
def test_no_types_and_no_racks_read_as_one_type_in_one_rack(case, precision):
    kc, kl, _, _, seed, crossing = TYPED_INSTANCES[case]
    prev, logical, gangs = _instance(kc, kl, seed, crossing)
    one_type, one_rack = np.full(kc, "a100"), np.zeros(kc, np.int64)
    cost, phys, node_map = reference.relabel(prev, logical, gangs, precision)
    cost1, phys1, node_map1 = reference.relabel(prev, logical, gangs, precision,
                                                types=one_type, racks=one_rack)
    assert cost == cost1
    assert np.array_equal(phys, phys1) and np.array_equal(node_map, node_map1)
    assert reference.plan_cost(prev, phys, gangs, logical) == reference.plan_cost(
        prev, phys, gangs, logical, node_map=node_map, racks=one_rack)
    active = np.array(list(gangs))
    assert reference.plan_problems(phys, active, gangs, logical) == reference.plan_problems(
        phys, active, gangs, logical, types=one_type, node_map=node_map)


def test_the_program_is_correct_on_a_typed_racked_cluster():
    res = harness.run(typed(), 2**31 + 7, 0.5, False, time.perf_counter(), FAKE_DEVICE)
    assert res["correct"] is True, res["checks"]
    rounds = res["_rounds"]
    assert rounds and all(r.migration is not None for r in rounds)
    # the type and rack terms are at work: without them the same rounds fail
    assert harness.check(rounds, res["_gangs"])["cost_gap"]["value"] > 0


def _hosts_swapped_across_types(prev, logical, gangs, res):
    """Two logical nodes of two GPU types trade their physical nodes."""
    na = np.array(res.node_assignment, np.int64)
    types = np.array(prev.cluster.node_types())
    slots = res.physical_plan.slots
    a = next(lo for lo in range(na.size) if types[lo] == "a100" and (slots[na[lo]] != -1).any())
    b = next(lo for lo in range(na.size) if types[lo] == "v100"
             and not np.array_equal(slots[na[lo]], slots[na[a]]))
    swapped = slots.copy()
    swapped[[na[a], na[b]]] = slots[[na[b], na[a]]]
    na[[a, b]] = na[[b, a]]
    return _result(prev, swapped, res.matching_cost, na)


def _rack_term_dropped(prev, logical, gangs, res):
    cl = prev.cluster
    racks = np.array([cl.rack_of(k) for k in range(cl.num_nodes)])
    na = np.asarray(res.node_assignment)
    res.matching_cost -= 0.5 * int((racks[na] != racks).sum())
    return res


@pytest.mark.parametrize(
    "fault,number", [(_hosts_swapped_across_types, "invalid_plans"),
                     (_rack_term_dropped, "cost_gap")],
    ids=["plan-relabelled-across-types", "cost-without-rack-term"],
)
def test_a_fault_on_a_typed_racked_cluster_is_not_correct(monkeypatch, fault, number):
    _fused_plan_patched(monkeypatch, fault)
    res, _ = _run(typed())
    assert res["correct"] is False
    assert res["checks"][number]["value"] > 0


@pytest.mark.parametrize("config", ["shockwave-512x4", "helios-saturn-262x8"])
def test_a_cluster_without_types_or_racks_builds_the_plain_spec(config):
    from repro.core.cluster import ClusterSpec

    cl = harness.load_json(os.path.join(ROOT, "bench", "configs", config + ".json"))["cluster"]
    assert harness.cluster_spec(cl) == ClusterSpec(cl["num_nodes"], cl["gpus_per_node"])
    assert harness.layout(cl) == (None, None)


def test_a_typed_racked_cluster_builds_its_spec():
    from repro.core.cluster import ClusterSpec

    cl = typed(8, 3)["config"]["cluster"]
    want = ClusterSpec(8, 4, node_gpu_types=("a100",) * 4 + ("v100",) * 4, nodes_per_rack=3)
    assert harness.cluster_spec(cl) == want
    types, racks = harness.layout(cl)
    assert types.tolist() == list(want.node_types())
    assert racks.tolist() == [want.rack_of(k) for k in range(8)]


@pytest.mark.parametrize("extra", [
    {"gpu_type": "v100"},
    {"node_gpu_types": [["a100", 8], ["v100", 7]]},
    {"node_gpu_types": [["a100", 8], ["h100", 8]]},
    {"node_gpu_types": [["a100", 16.0]]},
    {"nodes_per_rack": -1},
    {"nodes_per_rack": "4"},
], ids=["unknown-key", "counts-short", "unknown-type", "count-not-whole", "racks-negative",
        "racks-not-a-number"])
def test_a_cluster_key_the_harness_cannot_honour_raises(extra):
    cl = dict(num_nodes=16, gpus_per_node=4, **extra)
    with pytest.raises(ValueError):
        harness.cluster_spec(cl)
