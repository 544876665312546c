"""Observability-layer suite: inertness, deterministic tracing, metric
views, exports, crash-resume reseeding, and lint scoping.

The contract under test (``src/repro/obs``):

* **inert when disabled** — ``obs=None`` replays are bit-identical to
  each other and to the uninstrumented seed path (every call site routes
  through the ``NULL_TRACER`` no-op singleton);
* **inert when enabled** — tracing adds host-side bookkeeping only: an
  obs-enabled replay makes the SAME decisions as a plain one, for both
  the host and the fused migrate arms;
* **deterministic** — the timing-free span-tree fingerprint and the
  ``deterministic_snapshot()`` of the metrics registry are identical
  across two seeded runs (wall-clock histograms are excluded by design);
* **exact** — histogram percentiles are nearest-rank, not interpolated;
* **exportable** — the Chrome-trace/Perfetto document and the versioned
  ``tesserae-obs-v1`` document both pass their validators;
* **consolidated** — ``SimResult``'s telemetry views (``degrade_counts``,
  ``warm_hit_rounds``, ``total_bid_iters``, ``fused_host_fallbacks``)
  are registry reads that equal the legacy per-round aggregations they
  replaced, and crash-resume reseeds the registry to exactly the
  uninterrupted run's content;
* **lint-scoped** — the tessalint ``sync`` / ``det`` passes cover
  ``src/repro/obs`` (a stray device readout or wall clock there fails
  the lint; ``time.perf_counter`` stays sanctioned).
"""

import contextlib
import json
import textwrap
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from repro.core.cluster import ClusterSpec
from repro.core.policies import TiresiasPolicy
from repro.core.profiler import ThroughputProfile
from repro.core.scheduler import DegradeReason, TesseraeScheduler
from repro.core.simulator import SimConfig, Simulator
from repro.core.traces import shockwave_trace
from repro.obs import (
    NULL_TRACER,
    OBS_SCHEMA_VERSION,
    Histogram,
    MetricsRegistry,
    Observability,
    Tracer,
    to_chrome_trace,
    to_obs_doc,
    tracer_of,
    validate_chrome_trace,
    validate_obs_doc,
    write_chrome_trace,
)

#: replay shape: 12 jobs at ~220/h on 16 GPUs run 50+ contended rounds
#: with warm hits in nearly every one (the same regime perf_summary's
#: fresh gate replays).
N_JOBS = 12
SEED = 5
MIN_ROUNDS = 20


@pytest.fixture(scope="module")
def profile():
    return ThroughputProfile()


def _mk_sched(cluster, profile, fused=False):
    return TesseraeScheduler(
        cluster,
        TiresiasPolicy(profile),
        profile,
        lap_backend="auction",
        tie_break=fused,
        fused_fanout=fused,
    )


def _run(profile, obs=None, fused=False, cfg=None, sched=None):
    cluster = ClusterSpec(4, 4)
    trace = shockwave_trace(
        num_jobs=N_JOBS, arrival_rate_per_hour=220.0, seed=SEED, profile=profile
    )
    sched = sched or _mk_sched(cluster, profile, fused=fused)
    return Simulator(cluster, trace, sched, profile, cfg, obs=obs).run()


def _fingerprint(res):
    """The decision-relevant outcome of a run (no wall times)."""
    return {
        "jobs": {
            jid: (s.finish_time, s.iters_done, s.migrations)
            for jid, s in res.jobs.items()
        },
        "makespan": res.makespan_s,
        "migrations": res.total_migrations,
        "rounds": res.num_rounds,
        "degrade": tuple(res.degrade_rounds),
        "match_rounds": res.match_rounds,
    }


# --------------------------------------------------------------------------- #
# Inertness
# --------------------------------------------------------------------------- #
class TestInert:
    def test_disabled_obs_replay_is_bit_identical(self, profile):
        a = _run(profile)
        b = _run(profile)
        assert a.num_rounds >= MIN_ROUNDS
        assert _fingerprint(a) == _fingerprint(b)

    @pytest.mark.parametrize("fused", [False, True], ids=["host", "fused"])
    def test_enabled_obs_is_decision_invariant(self, profile, fused):
        plain = _run(profile, fused=fused)
        obs = Observability()
        traced = _run(profile, obs=obs, fused=fused)
        assert _fingerprint(plain) == _fingerprint(traced)
        # ...and the run was actually traced, not silently skipped
        assert obs.tracer.roots()

    def test_tracer_of_none_is_the_null_singleton(self):
        assert tracer_of(None) is NULL_TRACER
        # the no-op protocol: span() nests, annotates, and records nothing
        with NULL_TRACER.span("decide", jobs=3) as sp:
            sp.annotate(placed=1)
            with NULL_TRACER.span("inner"):
                pass
        assert NULL_TRACER.roots() == []


# --------------------------------------------------------------------------- #
# Tracer determinism + span catalog
# --------------------------------------------------------------------------- #
class TestTracer:
    def _span_names(self, tracer):
        names = set()

        def walk(node):
            names.add(node["name"])
            for c in node.get("children", ()):
                walk(c)

        for root in tracer.structure():
            walk(root)
        return names

    def test_fingerprint_identical_across_two_seeded_runs(self, profile):
        obs1, obs2 = Observability(), Observability()
        _run(profile, obs=obs1, fused=True)
        _run(profile, obs=obs2, fused=True)
        fp1, fp2 = obs1.tracer.fingerprint(), obs2.tracer.fingerprint()
        assert fp1 == fp2
        assert len(fp1) == 64 and int(fp1, 16) >= 0  # sha256 hex

    def test_host_arm_span_catalog(self, profile):
        obs = Observability()
        _run(profile, obs=obs)
        names = self._span_names(obs.tracer)
        assert {
            "round",
            "decide",
            "policy_sort",
            "place",
            "pack",
            "lap.solve",
            "migrate.host",
            "advance_round",
        } <= names
        assert "migrate.fused" not in names

    def test_fused_arm_span_catalog(self, profile):
        obs = Observability()
        res = _run(profile, obs=obs, fused=True)
        names = self._span_names(obs.tracer)
        assert {
            "migrate.fused",
            "migrate.fused.program",
            "migrate.fused.readout",
        } <= names
        # one sanctioned readout per fused round, zero host fallbacks

        def count(node, name):
            return (node["name"] == name) + sum(
                count(c, name) for c in node.get("children", ())
            )

        structure = obs.tracer.structure()
        readouts = sum(count(r, "migrate.fused.readout") for r in structure)
        fallbacks = sum(
            count(r, "migrate.fused.host_fallback") for r in structure
        )
        assert readouts == res.metrics.counter_value("match.fused_rounds")
        assert fallbacks == 0

    def test_spans_nest_under_decide(self, profile):
        obs = Observability()
        _run(profile, obs=obs)
        decides = [
            c
            for root in obs.tracer.structure()
            if root["name"] == "round"
            for c in root.get("children", ())
            if c["name"] == "decide"
        ]
        assert decides
        for d in decides:
            child_names = [c["name"] for c in d.get("children", ())]
            assert child_names[0] == "policy_sort"
            assert "place" in child_names and "pack" in child_names

    def test_explicit_spans_record_attrs_and_timings(self):
        t = Tracer()
        with t.span("outer", k=1) as sp:
            sp.annotate(result="ok")
            with t.span("inner"):
                pass
        (root,) = t.roots()
        assert root.name == "outer"
        assert root.attrs == {"k": 1, "result": "ok"}
        assert [c.name for c in root.children] == ["inner"]
        assert root.dur_s >= root.children[0].dur_s >= 0.0

    def test_annotate_enters_one_annotation_per_span_in_nesting_order(self):
        log = []

        @contextlib.contextmanager
        def annotate(name):
            log.append(("enter", name))
            yield
            log.append(("exit", name))

        t = Tracer(annotate=annotate)
        with t.span("outer"):
            with t.span("inner"):
                pass
        assert log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]
        # an exception unwinds a child its own context never closed:
        # the child's annotation exits before its parent's
        log.clear()
        with pytest.raises(RuntimeError):
            with t.span("a"):
                t.span("b")
                raise RuntimeError("boom")
        assert log == [("enter", "a"), ("enter", "b"), ("exit", "b"), ("exit", "a")]
        with t.span("c"):
            pass  # the stack is clean again: c is a root, not b's child
        assert [r.name for r in t.roots()] == ["outer", "a", "c"]

    def test_annotated_replay_annotates_every_span_and_decides_the_same(
        self, profile
    ):
        opened, closed = Counter(), Counter()

        @contextlib.contextmanager
        def annotate(name):
            opened[name] += 1
            yield
            closed[name] += 1

        plain = _run(profile, fused=True)
        obs = Observability(tracer=Tracer(annotate=annotate))
        traced = _run(profile, obs=obs, fused=True)
        assert _fingerprint(plain) == _fingerprint(traced)

        spans = Counter()

        def walk(node):
            spans[node["name"]] += 1
            for c in node.get("children", ()):
                walk(c)

        for root in obs.tracer.structure():
            walk(root)
        # compilations are annotated too, but are no spans
        assert {k: v for k, v in opened.items() if not k.startswith("compile:")} == spans
        assert opened == closed
        assert {"migrate.fused.prepare", "migrate.fused.finish", "pack.graph",
                "pack.apply", "lap.prologue", "active_scan"} <= set(spans)

    def test_obs_package_imports_no_jax(self):
        import subprocess
        import sys

        code = ("import sys, repro.obs; "
                "sys.exit(any(m == 'jax' or m.startswith('jax.') for m in sys.modules))")
        src = str(Path(__file__).resolve().parents[1] / "src")
        out = subprocess.run([sys.executable, "-c", code], env={"PYTHONPATH": src},
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr


# --------------------------------------------------------------------------- #
# Compilations counted while an Observability is attached
# --------------------------------------------------------------------------- #
class TestCompileCounter:
    def test_counts_a_fresh_jit_once_by_name_until_detached(self, profile):
        import jax

        log = []

        def annotate(name):
            log.append(name)
            return contextlib.nullcontext()

        obs = Observability(tracer=Tracer(annotate=annotate))
        sched = _mk_sched(ClusterSpec(4, 4), profile)
        sched.set_observability(obs)

        def fresh_counted_fn(x):
            return x * 3.0 + 1.0

        f = jax.jit(fresh_counted_fn)
        x7 = np.arange(7.0, dtype=np.float32)
        f(x7)
        f(x7)  # same shapes: no compilation
        name = "jax.compiles.jit(fresh_counted_fn)"
        assert obs.metrics.counter_value(name) == 1
        assert "compile:jit(fresh_counted_fn)" in log
        # process-dependent, so not part of the deterministic snapshot
        assert name in obs.metrics.snapshot()["counters"]
        assert name not in obs.metrics.deterministic_snapshot()["counters"]

        sched.set_observability(None)
        f(np.arange(3.0, dtype=np.float32))  # compiles, counted by nobody
        assert obs.metrics.counter_value(name) == 1

    def test_a_persistent_cache_load_is_not_counted(self):
        import jax

        from repro.compile_cache import CACHE_HIT_EVENT, COMPILE_EVENT, CompileCounter

        obs = Observability()
        counter = CompileCounter(obs)
        try:
            for hit in (True, False):
                jax.monitoring.record_scalar(COMPILE_EVENT, 0.0, fun_name="jit(g)")
                if hit:
                    jax.monitoring.record_event(CACHE_HIT_EVENT)
                jax.monitoring.record_event_time_span(
                    COMPILE_EVENT, 0.0, 1.0, fun_name="jit(g)")
        finally:
            counter.close()
        assert obs.metrics.counter_value("jax.compiles.jit(g)") == 1


# --------------------------------------------------------------------------- #
# Metrics: exactness + registry views
# --------------------------------------------------------------------------- #
class TestMetrics:
    def test_percentiles_are_nearest_rank_exact(self):
        h = Histogram("x")
        for v in range(1, 101):
            h.observe(float(v))
        assert h.percentile(50) == 50.0
        assert h.percentile(95) == 95.0
        assert h.percentile(99) == 99.0
        assert h.percentile(100) == 100.0
        single = Histogram("y")
        single.observe(7.0)
        assert single.percentile(50) == single.percentile(99) == 7.0
        with pytest.raises(ValueError):
            Histogram("empty").percentile(50)

    def test_simresult_views_equal_legacy_aggregations(self, profile):
        res = _run(profile, fused=True)
        rounds = res.match_rounds
        assert res.total_bid_iters == sum(
            int(rs.get("bid_iters", 0)) for rs in rounds
        )
        legacy_warm = sum(
            1 for rs in rounds[1:] if rs.get("warm_instances", 0) > 0
        )
        assert res.warm_hit_rounds(skip=1) == legacy_warm > 0
        assert res.fused_host_fallbacks == sum(
            int(rs.get("fused_host_fallbacks", 0)) for rs in rounds
        )
        assert res.degrade_counts == dict(Counter(res.degrade_rounds))

    def test_degrade_counts_view_under_forced_degradation(self, profile):
        # a 0-second decide deadline trips the ladder every round
        sched = _mk_sched(ClusterSpec(4, 4), profile)
        sched.decide_deadline_s = 0.0
        res = _run(profile, sched=sched)
        assert res.degrade_counts == dict(Counter(res.degrade_rounds))
        degraded = {
            k: v
            for k, v in res.degrade_counts.items()
            if k != DegradeReason.NONE
        }
        assert degraded, "0s deadline must force the degradation ladder"

    def test_deterministic_snapshot_excludes_timing(self, profile):
        obs1, obs2 = Observability(), Observability()
        _run(profile, obs=obs1)
        _run(profile, obs=obs2)
        snap1 = obs1.metrics.deterministic_snapshot()
        snap2 = obs2.metrics.deterministic_snapshot()
        assert snap1 == snap2
        flat = json.dumps(snap1)
        assert "decide.latency_s" not in flat
        assert "decide.stage." not in flat
        # ...while the full snapshot does carry the timing histograms
        assert "decide.latency_s" in json.dumps(obs1.metrics.snapshot())

    def test_summary_carries_decide_percentiles(self, profile):
        res = _run(profile)
        s = res.summary()
        assert s["decide_p50_s"] >= 0.0
        assert s["decide_p99_s"] >= s["decide_p50_s"]

    def test_registry_prefix_and_default_reads(self):
        m = MetricsRegistry()
        m.counter("sim.degrade.none").inc(3)
        m.counter("sim.degrade.deadline-host").inc()
        assert m.counters_with_prefix("sim.degrade.") == {
            "none": 3,
            "deadline-host": 1,
        }
        assert m.counter_value("absent") == 0
        assert m.histogram_values("absent") == []


# --------------------------------------------------------------------------- #
# Exports
# --------------------------------------------------------------------------- #
class TestExport:
    def test_chrome_trace_valid_and_json_roundtrips(self, profile, tmp_path):
        obs = Observability()
        _run(profile, obs=obs, fused=True)
        path = tmp_path / "trace.json"
        write_chrome_trace(obs.tracer, str(path))
        doc = json.loads(path.read_text())
        assert validate_chrome_trace(doc) == []
        events = doc["traceEvents"]
        assert events and all(e["ph"] == "X" for e in events)
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in events)
        assert doc["otherData"]["schema"] == OBS_SCHEMA_VERSION
        names = {e["name"] for e in events}
        assert {"round", "decide", "migrate.fused"} <= names

    def test_obs_doc_valid(self, profile):
        obs = Observability()
        _run(profile, obs=obs)
        doc = to_obs_doc(obs.tracer, obs.metrics)
        assert doc["version"] == OBS_SCHEMA_VERSION
        assert validate_obs_doc(doc) == []
        assert doc["fingerprint"] == obs.tracer.fingerprint()

    def test_validators_reject_corruption(self, profile):
        obs = Observability()
        _run(profile, obs=obs)
        bad = to_obs_doc(obs.tracer, obs.metrics)
        bad["version"] = "tesserae-obs-v0"
        assert validate_obs_doc(bad)
        chrome = to_chrome_trace(obs.tracer)
        chrome["traceEvents"][0].pop("ts")
        assert validate_chrome_trace(chrome)


# --------------------------------------------------------------------------- #
# Crash-resume: the registry reseeds to the uninterrupted run's content
# --------------------------------------------------------------------------- #
class TestResume:
    def test_resume_reseeds_metrics_exactly(self, profile, tmp_path):
        baseline = _run(profile)
        cluster = ClusterSpec(4, 4)
        trace = shockwave_trace(
            num_jobs=N_JOBS,
            arrival_rate_per_hour=220.0,
            seed=SEED,
            profile=profile,
        )
        victim = Simulator(cluster, trace, _mk_sched(cluster, profile), profile)
        assert victim.run(stop_after_rounds=5) is None
        snap = str(tmp_path / "snap.npz")
        victim.save_state(snap)
        resumed = Simulator(
            cluster, trace, _mk_sched(cluster, profile), profile
        )
        resumed.load_state(snap)
        res = resumed.run()
        assert _fingerprint(res) == _fingerprint(baseline)
        assert (
            res.metrics.deterministic_snapshot()
            == baseline.metrics.deterministic_snapshot()
        )


# --------------------------------------------------------------------------- #
# Lint scoping (the tessalint manifest covers src/repro/obs)
# --------------------------------------------------------------------------- #
class TestLintScoping:
    @pytest.fixture()
    def lint(self):
        from tools.tessalint import Manifest, lint_file
        from tools.tessalint.manifest import DEFAULT_MANIFEST_PATH

        man = Manifest.load(DEFAULT_MANIFEST_PATH)

        def run(tmp_path, source, filename):
            p = tmp_path / "src" / "repro" / "obs" / filename
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(source))
            return [f for f in lint_file(p, man) if not f.suppressed]

        return run

    def test_stray_device_readout_in_obs_fails_sync(self, lint, tmp_path):
        live = lint(
            tmp_path,
            """\
            import jax
            import jax.numpy as jnp
            import numpy as np

            def snapshot_device_val(device_val: jax.Array):
                return np.asarray(device_val)
            """,
            "probe.py",
        )
        assert any(f.rule == "sync" for f in live), [
            f.format_text() for f in live
        ]

    def test_wall_clock_in_obs_fails_det_perf_counter_clean(
        self, lint, tmp_path
    ):
        live = lint(
            tmp_path,
            """\
            import time

            def stamp():
                return time.time()
            """,
            "clocky.py",
        )
        assert any(f.rule == "det" for f in live)
        assert not lint(
            tmp_path,
            """\
            import time

            def stamp():
                return time.perf_counter()
            """,
            "clean.py",
        )

    def test_real_obs_modules_lint_clean(self):
        from tools.tessalint import Manifest, lint_file
        from tools.tessalint.manifest import DEFAULT_MANIFEST_PATH

        man = Manifest.load(DEFAULT_MANIFEST_PATH)
        repo = Path(__file__).resolve().parents[1]
        obs_dir = repo / "src" / "repro" / "obs"
        files = sorted(obs_dir.glob("*.py"))
        assert files
        for p in files:
            live = [f for f in lint_file(p, man) if not f.suppressed]
            assert live == [], [f.format_text() for f in live]


# --------------------------------------------------------------------------- #
# BENCH regression gate (file-only arm of perf_summary --check)
# --------------------------------------------------------------------------- #
class TestCheckGate:
    def test_committed_bench_files_pass_the_gate(self, capsys):
        from benchmarks.perf_summary import run_check

        assert run_check(fresh=False) == 0
        out = capsys.readouterr().out
        assert "0 failed" in out
