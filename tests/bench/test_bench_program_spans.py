"""The program's spans, compile counts and named scopes as the benchmark reads
them: the metric readers, the span-named idle gaps and the per-scope device
time, on hand-built records and traces, a small CPU run, and a trace
recorded on the chip."""

import glob
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, program_spans  # noqa: E402

FIXTURES = os.path.join(ROOT, "bench", "testdata", "spans")


def _round(spans, stats=None, compiles=None):
    return {"decide_s": 1.0, "round_s": 1.2, "timings": {}, "stats": stats or {},
            "degrade": "none", "active": 10, "spans": spans, "compiles": compiles or {}}


def _record():
    """Two window rounds and a traced window, with what the program records."""
    r0 = _round(
        {"active_scan": 0.1,
         "round": 0.01,
         "round/decide/pack": 0.02,
         "round/decide/pack/pack.graph": 0.3,
         "round/decide/pack/lap.solve": 0.2,
         "round/decide/pack/lap.solve/lap.prologue": 0.05,
         "round/decide/migrate.fused": 0.01,
         "round/decide/migrate.fused/migrate.fused.prepare": 0.2,
         "round/decide/migrate.fused/migrate.fused.program": 2.0,
         "round/decide/migrate.fused/migrate.fused.readout": 0.03,
         "round/decide/migrate.fused/migrate.fused.finish": 0.06},
        {"fused_pair_trips": 300, "fused_node_iters": 40, "fused_bid_iters": 1000},
        {"jit(_pad)": 1},
    )
    r1 = _round(
        {"active_scan": 0.3,
         "round/decide/pack/pack.graph": 0.1,
         "round/decide/pack/lap.solve": 0.4,
         "round/decide/migrate.fused/migrate.fused.program": 4.0,
         "round/decide/migrate.fused/migrate.fused.prepare": 0.1,
         "round/decide/migrate.host/lap.solve": 9.0},
        {"fused_pair_trips": 500, "fused_bid_iters": 1000},
        {"jit(_pad)": 1, "jit(_match_prologue_dev)": 1},
    )
    trace = {"busy_s": 1.0, "window_s": 2.0, "fused_device_s_per_round": 0.5,
             "scope_device_s": {"pair_auction": 0.4, "node_match": 0.05}}
    return {"setup_s": 1.0, "window_s": 10.0, "rounds": [r0, r1], "trace": trace}


@pytest.mark.parametrize("name,want", [
    ("pair_loop_trips", 400.0),
    ("node_match_iters", 20.0),
    ("migrate_host_s", ((0.01 + 0.2 + 0.03 + 0.06) + 0.1) / 2),
    ("pack_graph_s", (0.3 + 0.1) / 2),
    ("pack_lap_s", (0.25 + 0.4) / 2),
    ("sim_scan_s", 0.2),
    ("compiles_per_round", 1.5),
    ("pair_auction_device_s", 0.4),
    ("node_match_device_s", 0.05),
])
def test_each_new_reader_on_a_hand_built_record(name, want):
    assert name in program_spans.METRICS
    assert harness.metric_reader(name)(_record()) == pytest.approx(want)


@pytest.mark.parametrize("name", program_spans.METRICS)
def test_each_new_reader_reads_nothing_where_the_program_records_nothing(name):
    """A record from a program without the spans, compile counts, trip
    counters and named scopes."""
    record = _record()
    for r in record["rounds"]:
        del r["spans"], r["compiles"]
        r["stats"] = {"fused_bid_iters": 1000}
    del record["trace"]["scope_device_s"]
    assert harness.metric_reader(name)(record) is None
    record["trace"] = None
    assert harness.metric_reader(name)(record) is None


def test_labels_name_the_innermost_program_span_from_decide_on():
    P = program_spans.PREFIX
    label = program_spans.label
    assert label(["bench.round", P + "round", "bench.decide", P + "decide", P + "pack",
                  P + "lap.solve", P + "lap.prologue", P + "compile:jit(_pad)"]
                 ) == "decide/pack/lap.solve/lap.prologue/compile:jit(_pad)"
    assert label(["bench.round", P + "active_scan"]) == "sim/active_scan"
    assert label(["bench.round", P + "round", P + "advance_round"]) == "sim/advance_round"
    assert label(["bench.round", P + "round"]) == "sim/round"
    assert label(["bench.round", P + "round", "bench.decide"]) == "decide"
    assert label(["bench.round"]) == "sim"
    assert label(["python_function"]) is None


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ns * 1000)} "
            f"duration_ps: {int(dur_ns * 1000)} }}")


def _hand_built_trace():
    """One round of 100 us.  Host: the harness's annotations and the
    program's spans, nested; device: the fused module at 60..90 us, in it a
    ``while`` with a nested op (pair_auction) and one node-match op."""
    P = program_spans.PREFIX
    names = ["bench.round", "bench.decide", P + "round", P + "decide", P + "pack",
             P + "lap.solve", P + "migrate.fused", P + "migrate.fused.program",
             P + "active_scan"]
    meta = "\n".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} name: "{n}" }} }}'
                     for i, n in enumerate(names))
    host = "\n".join([
        _event(1, 0, 100_000),       # bench.round
        _event(9, 2_000, 6_000),     # active_scan
        _event(3, 10_000, 85_000),   # round
        _event(2, 12_000, 80_000),   # bench.decide
        _event(4, 13_000, 78_000),   # decide
        _event(5, 15_000, 40_000),   # pack
        _event(6, 20_000, 30_000),   # lap.solve
        _event(7, 55_000, 35_000),   # migrate.fused
        _event(8, 58_000, 32_000),   # migrate.fused.program
    ])
    ops = "\n".join([
        _event(1, 1_000, 1_000),     # an op of another module (pack prologue)
        _event(2, 60_000, 20_000),   # while.5 (pair_auction)
        _event(3, 62_000, 5_000),    # fusion.9 inside it (pair_auction)
        _event(4, 82_000, 6_000),    # fusion.12 (node_match)
        _event(5, 88_000, 2_000),    # copy.1 (no scope)
    ])
    modules = "\n".join([_event(6, 1_000, 1_000), _event(7, 60_000, 30_000)])
    return f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host} }}
{meta}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{ops} }}
  lines {{ id: 3 name: "XLA Modules" timestamp_ns: 0
{modules} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.9 = f32[4]{{0}} fusion(f32[4]{{0}} %p)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%while.5 = (s32[]) while((s32[]) %t)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.9 = f32[4]{{0}} fusion(f32[4]{{0}} %p)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%fusion.12 = f32[4]{{0}} fusion(f32[4]{{0}} %q)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%copy.1 = f32[4]{{0}} copy(f32[4]{{0}} %r)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "jit__pad(1)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "jit__fused_round(2)" }} }}
}}
"""


SCOPES = {"while.5": "pair_auction", "fusion.9": "pair_auction", "fusion.12": "node_match"}


def test_reduce_names_idle_gaps_by_program_spans_and_times_each_scope():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(_hand_built_trace())
    totals = {}
    for name, ns in program_spans.idle_pieces(pd):
        totals[name] = totals.get(name, 0.0) + ns
    # idle 0..1, 2..60, 80..82, 90..100 us, cut where the open spans change
    assert totals == pytest.approx({
        "sim": 1_000 + 2_000 + 5_000, "sim/active_scan": 6_000,
        "sim/round": 2_000 + 3_000, "decide": 3_000 + 2_000,
        "decide/pack": 5_000 + 5_000, "decide/pack/lap.solve": 30_000,
        "decide/migrate.fused": 3_000,
        "decide/migrate.fused/migrate.fused.program": 2_000 + 2_000,
    })
    got = program_spans.reduce(pd, SCOPES)
    gaps = got["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and gaps[0] == ["decide/pack/lap.solve", pytest.approx(30e-6)]
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert got["idle_named_share"] == pytest.approx(58 / 71)
    # the while's union (its nested op not again), and the node match; the
    # op of the same name in another module is not the fused program's
    assert got["scope_device_s"] == pytest.approx({"pair_auction": 20e-6,
                                                   "node_match": 6e-6})
    assert got["scope_coverage"] == pytest.approx(26 / 30)
    assert got["fused_device_s_per_round"] == pytest.approx(30e-6)
    assert program_spans.program_spans_vs_modules(pd) == [(32_000, 30_000)]


def test_a_trace_without_program_spans_keeps_the_stage_reconstruction():
    from jax.profiler import ProfileData

    text = _hand_built_trace().replace(program_spans.PREFIX, "other/")
    got = program_spans.reduce(ProfileData.from_text_proto(text))
    assert "idle_named_share" not in got and "scope_device_s" not in got
    assert {name for name, _ in got["breakdown"]["idle_gaps"]} <= {"sim", "decide", "bench"}


def test_scope_map_reads_the_op_names_of_the_compiled_program():
    from repro.core.fused import lower_fused_round

    smap = program_spans.scope_map(lower_fused_round(4, 4, 2, 40).compile().as_text())
    assert set(smap.values()) == set(program_spans.SCOPES)
    text = ('  %while.3 = (s32[]) while(%t), condition=%c, body=%b, '
            'metadata={op_name="jit(_fused_round)/pair_auction/while" source_file="f.py"}\n'
            '  ROOT %tuple.1 = (s32[]) tuple(%w), metadata={op_name="jit(_fused_round)"}\n')
    assert program_spans.scope_map(text) == {"while.3": "pair_auction"}


def test_a_small_cpu_run_records_spans_and_compiles(monkeypatch):
    spec = harness.cell("shockwave-512x4.saturated")
    spec["config"]["cluster"]["num_nodes"] = 16
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    res = program_spans.run(spec, 2**31 + 7, 0.5, False, time.perf_counter(), device)
    assert res["correct"] is True
    got = res["metrics"]
    assert set(program_spans.METRICS) - set(got) == {"pair_auction_device_s",
                                                     "node_match_device_s"}
    assert got["pair_loop_trips"] > 0 and got["node_match_iters"] > 0
    assert got["pair_loop_trips"] < got["bid_iters_per_round"]
    assert got["pack_graph_s"] + got["pack_lap_s"] <= got["pack_s"]
    assert 0 < got["migrate_host_s"] < got["migrate_s"]
    assert got["compiles_per_round"] * res["attempted"] == res["compiled_in_window"]


def _fixtures():
    paths = sorted(glob.glob(os.path.join(FIXTURES, "*.xplane.pb.gz")))
    assert paths, "no recorded chip trace under bench/testdata/spans"
    for path in paths:
        with open(path.replace(".xplane.pb.gz", ".scopes.json")) as f:
            yield program_spans.trace_reduce.load(path), json.load(f)


def test_reduce_on_a_trace_with_program_spans_recorded_on_the_chip():
    for pd, smap in _fixtures():
        got = program_spans.reduce(pd, smap)
        assert got["rounds"] >= 2
        gaps = got["breakdown"]["idle_gaps"]
        assert all(name == "bench" or name.split("/")[0] in ("decide", "sim")
                   for name, _ in gaps)
        assert got["idle_named_share"] >= 0.9
        assert got["scope_coverage"] >= 0.95
        scopes = got["scope_device_s"]
        assert scopes["pair_auction"] > 0 and scopes["node_match"] > 0
        assert scopes["pair_auction"] + scopes["node_match"] <= got["fused_device_s_per_round"]
        # the program span ends when the device has finished
        spans = program_spans.program_spans_vs_modules(pd)
        assert len(spans) == got["rounds"]
        assert all(span_ns >= module_ns > 0 for span_ns, module_ns in spans)
