"""The reduction from a profiler trace to the benchmark's device numbers."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402


def test_union_merges_overlaps_and_clips():
    got = trace_reduce.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]


def test_gaps_are_the_complement_inside_the_window():
    busy = [(1, 3), (5, 12), (20, 25)]
    assert trace_reduce.gaps(busy, 0, 30) == [(0, 1), (3, 5), (12, 20), (25, 30)]
    assert trace_reduce.gaps([], 0, 4) == [(0, 4)]


def test_idle_time_is_named_by_the_host_segment_it_falls_in():
    decides = [(10.0, 50.0)]
    timings = [{"schedule_s": 0.0, "place_s": 5e-9, "pack_s": 10e-9, "migrate_s": 20e-9}]
    rounds = [(0.0, 60.0)]
    segs = trace_reduce.host_segments(decides, timings, rounds)
    assert segs == [(0.0, 10.0, "sim"), (10.0, 15.0, "decide/place"),
                    (15.0, 25.0, "decide/pack"), (25.0, 45.0, "decide/migrate"),
                    (45.0, 50.0, "decide"), (50.0, 60.0, "sim")]
    pieces = trace_reduce.attribute([(5.0, 20.0), (55.0, 70.0)], segs)
    assert pieces == [("sim", 5.0), ("decide/place", 5.0), ("decide/pack", 5.0),
                      ("sim", 5.0), ("bench", 10.0)]


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ns * 1000)} "
            f"duration_ps: {int(dur_ns * 1000)} }}")


def _hand_built_trace():
    """Two rounds of 100 us; the device runs two overlapping ops in each, one
    of them inside the fused module, and one op outside the window."""
    host = "\n".join([
        _event(1, 0, 100_000), _event(2, 10_000, 60_000),
        _event(1, 100_000, 100_000), _event(2, 110_000, 60_000),
    ])
    ops = "\n".join([
        _event(1, 40_000, 20_000), _event(2, 50_000, 20_000),  # union 30 us
        _event(1, 140_000, 10_000),                            # 10 us
        _event(2, 250_000, 50_000),                            # outside
    ])
    modules = "\n".join([_event(3, 40_000, 30_000), _event(3, 140_000, 10_000),
                         _event(4, 250_000, 50_000)])
    return f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.round" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.decide" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{ops} }}
  lines {{ id: 3 name: "XLA Modules" timestamp_ns: 0
{modules} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "while.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit__fused_round(7)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit_other" }} }}
}}
"""


def test_reduce_on_a_hand_built_trace():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(_hand_built_trace())
    timings = [{"schedule_s": 0.0, "place_s": 0.0, "pack_s": 20e-6, "migrate_s": 40e-6}] * 2
    got = trace_reduce.reduce(pd, timings)
    assert got["window_s"] == pytest.approx(200e-6)
    assert got["busy_s"] == pytest.approx(40e-6)
    assert got["rounds"] == 2
    assert got["fused_device_s_per_round"] == pytest.approx(20e-6)
    ops = dict(got["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 30e-6, "while.2": 20e-6})
    gaps = got["breakdown"]["idle_gaps"]
    assert sum(s for _, s in gaps) == pytest.approx(160e-6)
    # idle 0..40, 70..140 and 150..200 us, cut at the host's segments
    assert [name for name, _ in gaps[:5]] == ["sim", "sim", "decide/pack",
                                              "decide/pack", "decide/migrate"]
    assert [s for _, s in gaps[:5]] == pytest.approx([30e-6, 30e-6, 20e-6, 20e-6, 20e-6])


def test_reduce_on_a_trace_recorded_on_the_chip():
    paths = glob.glob(os.path.join(ROOT, "bench", "testdata", "*.xplane.pb.gz"))
    assert paths, "no recorded chip trace under bench/testdata"
    for path in paths:
        got = trace_reduce.reduce(trace_reduce.load(path), [])
        assert got["rounds"] >= 2
        assert 0.0 < got["busy_s"] <= got["window_s"]
        assert 0.0 < got["fused_device_s_per_round"] * got["rounds"] <= got["busy_s"]
        assert len(got["breakdown"]["device_ops"]) == 10
        assert all(label in ("sim", "bench") or label.startswith("decide")
                   for label, _ in got["breakdown"]["idle_gaps"])
