"""tessalint self-tests: per-rule positive/negative fixtures, pragma
suppression semantics, manifest scoping, the JSON schema round-trip, and
the "real tree lints clean" gate the CI lane enforces.
"""

import json
import textwrap
from pathlib import Path

import pytest

from tools.tessalint import JSON_VERSION, Finding, Manifest, lint_file, run_paths
from tools.tessalint.__main__ import main as cli_main
from tools.tessalint.findings import report
from tools.tessalint.manifest import (
    DEFAULT_MANIFEST_PATH,
    MANIFEST_VERSION,
    RuleConfig,
)
from tools.tessalint.passes import ALL_RULES

REPO_ROOT = Path(__file__).resolve().parents[1]

_JAX_PRELUDE = """\
import jax
import jax.numpy as jnp
import numpy as np
"""


def _lint(tmp_path, source, rule, options=None, filename="mod.py", rules=...):
    """Lint a fixture source with one rule scoped over it."""
    p = tmp_path / filename
    p.write_text(textwrap.dedent(source))
    man = Manifest({rule: RuleConfig(include=["*.py"], options=options or {})})
    if rules is ...:
        rules = [rule]
    return lint_file(p, man, rules=rules)


def _active(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


# --------------------------------------------------------------------------- #
# Rule: sync
# --------------------------------------------------------------------------- #
class TestSyncRule:
    @pytest.mark.parametrize(
        "body,needle",
        [
            # np.asarray on a device-annotated parameter
            ("def f(x: jax.Array):\n    return np.asarray(x)\n", "asarray"),
            # device_get is ALWAYS a flagged sync point
            ("def f(x: jax.Array):\n    return jax.device_get(x)\n", "device_get"),
            # float() coercion of a produced device value (taint chain)
            (
                "def f():\n    t = jnp.sum(jnp.ones(3))\n    u = t * 2\n"
                "    return float(u)\n",
                "coercion",
            ),
            # host control flow on a device value
            (
                "def f(x: jax.Array):\n    if x > 0:\n        return 1\n"
                "    return 0\n",
                "control flow",
            ),
            # .item() sync method
            ("def f(x: jax.Array):\n    return x.item()\n", ".item()"),
            # f-string formatting (P2)
            ("def f(x: jax.Array):\n    return f'{x}'\n", "f-string"),
            # a shard_map result is a device value
            (
                "def f(xs, g, m):\n"
                "    y = jax.shard_map(g, mesh=m, in_specs=None, out_specs=None)(xs)\n"
                "    return np.asarray(y)\n",
                "asarray",
            ),
        ],
    )
    def test_positive(self, tmp_path, body, needle):
        found = _active(_lint(tmp_path, _JAX_PRELUDE + body, "sync"), "sync")
        assert found, body
        assert any(needle in f.message for f in found)

    @pytest.mark.parametrize(
        "body",
        [
            # untainted argument: plain host conversion
            "def f(xs):\n    return np.asarray(xs)\n",
            # `is None` identity test never reads device data
            "def f(x: jax.Array):\n    if x is None:\n        return None\n"
            "    return x\n",
            # .ndim / .shape are host-side metadata
            "def f(x: jax.Array):\n    if x.ndim == 3:\n        return 1\n"
            "    return 0\n",
            # shape-derived ints are not tainted
            "def f(x: jax.Array):\n    n = x.shape[0]\n    if n > 2:\n"
            "        return n\n    return 0\n",
            # device math without any host crossing
            "def f(x: jax.Array):\n    return jnp.sum(x) * 2\n",
        ],
    )
    def test_negative(self, tmp_path, body):
        assert not _active(_lint(tmp_path, _JAX_PRELUDE + body, "sync"), "sync"), body

    def test_closure_inherits_taint(self, tmp_path):
        src = _JAX_PRELUDE + (
            "def outer(x: jax.Array):\n"
            "    def inner():\n"
            "        return float(x)\n"
            "    return inner\n"
        )
        assert _active(_lint(tmp_path, src, "sync"), "sync")

    def test_extra_producers_option(self, tmp_path):
        src = (
            "import numpy as np\nimport repro.kernels.ops as ops\n"
            "def f(a):\n    out = ops.lap_bid(a, a)\n    return np.asarray(out)\n"
        )
        # without the option the kernel result is not known to be device
        assert not _active(_lint(tmp_path, src, "sync"), "sync")
        found = _lint(
            tmp_path, src, "sync", options={"device_producers": ["repro.kernels."]}
        )
        assert _active(found, "sync")


# --------------------------------------------------------------------------- #
# Rule: det
# --------------------------------------------------------------------------- #
class TestDetRule:
    @pytest.mark.parametrize(
        "body,needle",
        [
            ("import time\ndef f():\n    return time.time()\n", "wall clock"),
            (
                "import numpy as np\ndef f():\n    return np.random.rand(3)\n",
                "legacy",
            ),
            (
                "import numpy as np\ndef f():\n"
                "    return np.random.default_rng()\n",
                "without a seed",
            ),
            ("import random\ndef f():\n    return random.random()\n", "stdlib RNG"),
            (
                "def f(xs):\n    return [x for x in set(xs)]\n",
                "iteration order",
            ),
            (
                "def f(xs, ys):\n    out = []\n"
                "    for v in set(xs).intersection(set(ys)):\n"
                "        out.append(v)\n    return out\n",
                "iteration order",
            ),
        ],
    )
    def test_positive(self, tmp_path, body, needle):
        found = _active(_lint(tmp_path, body, "det"), "det")
        assert found, body
        assert any(needle in f.message for f in found)

    @pytest.mark.parametrize(
        "body",
        [
            # durations may use perf_counter (the watchdog pattern)
            "import time\ndef f():\n    return time.perf_counter()\n",
            # seeded generator
            "import numpy as np\ndef f():\n    return np.random.default_rng(42)\n",
            # sorted() makes set order deterministic
            "def f(xs):\n    return [x for x in sorted(set(xs))]\n",
            # instance RNG with explicit seed
            "import random\ndef f():\n    return random.Random(7)\n",
            # list iteration is ordered
            "def f(xs):\n    return [x for x in list(xs)]\n",
        ],
    )
    def test_negative(self, tmp_path, body):
        assert not _active(_lint(tmp_path, body, "det"), "det"), body

    def test_dict_keys_opt_in(self, tmp_path):
        src = "def f(d):\n    return [k for k in d.keys()]\n"
        assert not _active(_lint(tmp_path, src, "det"), "det")
        found = _lint(tmp_path, src, "det", options={"flag_dict_keys": True})
        assert _active(found, "det")


# --------------------------------------------------------------------------- #
# Rule: jit
# --------------------------------------------------------------------------- #
class TestJitRule:
    @pytest.mark.parametrize(
        "body,needle",
        [
            (
                "import functools\nimport jax\n"
                "@functools.partial(jax.jit, static_argnames=('mode',))\n"
                "def f(x):\n    return x\n",
                "not a parameter",
            ),
            (
                "import jax\nCACHE = {}\n@jax.jit\ndef f(x):\n"
                "    return CACHE.get('k', 0) + x\n",
                "mutable",
            ),
            (
                "import jax\n@jax.jit\ndef f(x):\n    if x > 0:\n"
                "        return x\n    return -x\n",
                "control flow on traced parameter",
            ),
            (
                "import jax\n@jax.jit\ndef f(x):\n    global G\n    G = x\n"
                "    return x\n",
                "global",
            ),
            (
                "import jax\n@jax.jit\ndef f(x):\n    if x.shape[0] > 4:\n"
                "        return x * 2\n    return x\n",
                "recompiles",
            ),
            (
                "import jax\n"
                "@jax.jit(static_argnums=(3,))\n"
                "def f(x, y):\n    return x + y\n",
                "out of range",
            ),
        ],
    )
    def test_positive(self, tmp_path, body, needle):
        found = _active(_lint(tmp_path, body, "jit"), "jit")
        assert found, body
        assert any(needle in f.message for f in found)

    @pytest.mark.parametrize(
        "body",
        [
            # branching on a STATIC argument is the point of static args
            "import functools\nimport jax\n"
            "@functools.partial(jax.jit, static_argnames=('mode',))\n"
            "def f(x, mode):\n    if mode:\n        return x * 2\n    return x\n",
            # `is None` optional-arg dispatch is trace-time and idiomatic
            "import jax\n@jax.jit\ndef f(x, y=None):\n    if y is None:\n"
            "        return x\n    return x + y\n",
            # a shape branch that only raises is input validation
            "import jax\n@jax.jit\ndef f(x):\n    if x.ndim != 2:\n"
            "        raise ValueError('want 2-D')\n    return x\n",
            # module mutables are fine outside jit
            "CACHE = {}\ndef f(x):\n    return CACHE.get('k', 0) + x\n",
            # tuple module constant is not mutable capture
            "import jax\nDIMS = (1, 2)\n@jax.jit\ndef f(x):\n"
            "    return x + DIMS[0]\n",
        ],
    )
    def test_negative(self, tmp_path, body):
        assert not _active(_lint(tmp_path, body, "jit"), "jit"), body

    def test_jit_rebinding_form(self, tmp_path):
        src = (
            "import jax\ndef _f(x):\n    if x > 0:\n        return x\n"
            "    return -x\nf = jax.jit(_f)\n"
        )
        assert _active(_lint(tmp_path, src, "jit"), "jit")


# --------------------------------------------------------------------------- #
# Rule: mantissa
# --------------------------------------------------------------------------- #
class TestMantissaRule:
    WHOLE = {"functions": ["*"]}

    @pytest.mark.parametrize(
        "body,needle",
        [
            ("def plan():\n    pen = 0.3\n    return pen\n", "neither a half-unit"),
            (
                "def plan(total):\n    cost = total / 3.0\n    return cost\n",
                "unquantised division",
            ),
            (
                "def plan(base, n):\n    weights = base / n\n    return weights\n",
                "unquantised division",
            ),
        ],
    )
    def test_positive(self, tmp_path, body, needle):
        found = _active(_lint(tmp_path, body, "mantissa", options=self.WHOLE), "mantissa")
        assert found, body
        assert any(needle in f.message for f in found)

    @pytest.mark.parametrize(
        "body",
        [
            # half-units and powers of two are the allowed shapes
            "def plan():\n    pen = 1.5\n    scale = 0.25\n    return pen + scale\n",
            # power-of-two divisor keeps the lattice
            "def plan(total):\n    cost = total / 4.0\n    return cost\n",
            "def plan(total, k):\n    cost = total / 2**k\n    return cost\n",
            # non-cost-carrying names may divide freely
            "def plan(a):\n    tmp = a / 3\n    return tmp\n",
        ],
    )
    def test_negative(self, tmp_path, body):
        assert not _active(
            _lint(tmp_path, body, "mantissa", options=self.WHOLE), "mantissa"
        ), body

    def test_function_scoping(self, tmp_path):
        src = (
            "def scoped():\n    pen = 0.3\n    return pen\n"
            "def unscoped():\n    pen = 0.7\n    return pen\n"
        )
        found = _active(
            _lint(tmp_path, src, "mantissa", options={"functions": ["scoped"]}),
            "mantissa",
        )
        assert len(found) == 1 and found[0].line == 2


# --------------------------------------------------------------------------- #
# Rule: thread
# --------------------------------------------------------------------------- #
class TestThreadRule:
    @pytest.mark.parametrize(
        "body,needle",
        [
            # fire-and-forget: no join point anywhere in the function
            (
                "def run(self):\n    self.pool.submit(self.sched.prewarm)\n",
                "no join point",
            ),
            # owner touched between submit and join
            (
                "def run(self):\n"
                "    fut = self.pool.submit(self.sched.prewarm)\n"
                "    x = self.sched.stats\n"
                "    fut.result()\n"
                "    return x\n",
                "may still own",
            ),
            # threading.Thread(target=bound method), never joined
            (
                "import threading\n"
                "def go(self):\n"
                "    t = threading.Thread(target=self.ctx.poke)\n"
                "    t.start()\n",
                "no join point",
            ),
        ],
    )
    def test_positive(self, tmp_path, body, needle):
        found = _active(_lint(tmp_path, body, "thread"), "thread")
        assert found, body
        assert any(needle in f.message for f in found)

    @pytest.mark.parametrize(
        "body",
        [
            # the simulator pattern: join BEFORE touching the owner again
            "def run(self):\n"
            "    fut = self.pool.submit(self.sched.prewarm)\n"
            "    fut.result()\n"
            "    x = self.sched.stats\n"
            "    return x\n",
            # submitting a plain function shares no bound state
            "def run(self, work):\n"
            "    fut = self.pool.submit(work)\n"
            "    return fut\n",
            # no threading at all
            "def run(self):\n    return self.sched.stats\n",
        ],
    )
    def test_negative(self, tmp_path, body):
        assert not _active(_lint(tmp_path, body, "thread"), "thread"), body


# --------------------------------------------------------------------------- #
# Pragmas
# --------------------------------------------------------------------------- #
class TestPragmas:
    def test_suppression_with_reason(self, tmp_path):
        src = _JAX_PRELUDE + (
            "def f(x: jax.Array):\n"
            "    return np.asarray(x)  # tessalint: sync-ok(documented readout)\n"
        )
        found = _lint(tmp_path, src, "sync", rules=None)
        syncs = [f for f in found if f.rule == "sync"]
        assert syncs and all(f.suppressed for f in syncs)
        assert syncs[0].suppress_reason == "documented readout"
        assert not [f for f in found if f.rule == "pragma"]

    def test_bare_pragma_needs_reason(self, tmp_path):
        src = _JAX_PRELUDE + (
            "def f(x: jax.Array):\n"
            "    return np.asarray(x)  # tessalint: sync-ok()\n"
        )
        found = _lint(tmp_path, src, "sync", rules=None)
        assert any(
            f.rule == "pragma" and "no reason" in f.message for f in found
        )
        # and the empty pragma does NOT suppress
        assert _active(found, "sync")

    def test_unknown_rule_pragma(self, tmp_path):
        src = "x = 1  # tessalint: nosuchrule-ok(whatever)\n"
        found = _lint(tmp_path, src, "sync", rules=None)
        assert any(
            f.rule == "pragma" and "unknown rule" in f.message for f in found
        )

    def test_unused_pragma_flagged(self, tmp_path):
        src = _JAX_PRELUDE + (
            "def f(xs):\n"
            "    return np.asarray(xs)  # tessalint: sync-ok(stale excuse)\n"
        )
        found = _lint(tmp_path, src, "sync", rules=None)
        assert any(
            f.rule == "pragma" and "unused suppression" in f.message for f in found
        )

    def test_reason_may_contain_parens_and_commas(self, tmp_path):
        src = _JAX_PRELUDE + (
            "def f(x: jax.Array):\n"
            "    return np.asarray(x)"
            "  # tessalint: sync-ok(syncs only the (B,) verdict, see docstring)\n"
        )
        found = _lint(tmp_path, src, "sync", rules=None)
        syncs = [f for f in found if f.rule == "sync"]
        assert syncs and syncs[0].suppressed
        assert "(B,)" in syncs[0].suppress_reason
        assert not [f for f in found if f.rule == "pragma"]

    def test_multi_rule_pragma(self, tmp_path):
        src = _JAX_PRELUDE + (
            "import time\n"
            "def f(x: jax.Array):\n"
            "    return np.asarray(x), time.time()"
            "  # tessalint: sync-ok(readout), det-ok(telemetry only)\n"
        )
        p = tmp_path / "mod.py"
        p.write_text(src)
        man = Manifest(
            {
                "sync": RuleConfig(include=["*.py"]),
                "det": RuleConfig(include=["*.py"]),
            }
        )
        found = lint_file(p, man)
        assert found and all(f.suppressed for f in found if f.rule in ("sync", "det"))

    def test_pragma_on_any_line_of_multiline_expr(self, tmp_path):
        src = _JAX_PRELUDE + (
            "def f(x: jax.Array):\n"
            "    return np.asarray(  # tessalint: sync-ok(readout spans lines)\n"
            "        x\n"
            "    )\n"
        )
        found = _lint(tmp_path, src, "sync", rules=None)
        syncs = [f for f in found if f.rule == "sync"]
        assert syncs and all(f.suppressed for f in syncs)

    def test_syntax_error_reported_not_crashed(self, tmp_path):
        p = tmp_path / "broken.py"
        p.write_text("def f(:\n")
        man = Manifest({"sync": RuleConfig(include=["*.py"])})
        found = lint_file(p, man)
        assert len(found) == 1 and "does not parse" in found[0].message


# --------------------------------------------------------------------------- #
# Manifest scoping
# --------------------------------------------------------------------------- #
class TestManifest:
    SRC = _JAX_PRELUDE + "def f(x: jax.Array):\n    return np.asarray(x)\n"

    def test_rule_without_entry_runs_nowhere(self, tmp_path):
        p = tmp_path / "mod.py"
        p.write_text(self.SRC)
        assert lint_file(p, Manifest({}), rules=["sync"]) == []

    def test_include_exclude(self, tmp_path):
        (tmp_path / "core").mkdir()
        (tmp_path / "core" / "dev.py").write_text(self.SRC)
        (tmp_path / "core" / "host.py").write_text(self.SRC)
        man = Manifest(
            {
                "sync": RuleConfig(
                    include=["core/*.py"], exclude=["core/host.py"]
                )
            }
        )
        assert _active(lint_file(tmp_path / "core" / "dev.py", man), "sync")
        assert not _active(lint_file(tmp_path / "core" / "host.py", man), "sync")

    def test_suffix_matching_from_absolute_path(self, tmp_path):
        # the repo manifest says "src/repro/core/fused.py"; a fixture copy
        # living under an absolute tmp dir must still match
        d = tmp_path / "src" / "repro" / "core"
        d.mkdir(parents=True)
        p = d / "fused.py"
        p.write_text(self.SRC)
        man = Manifest({"sync": RuleConfig(include=["src/repro/core/fused.py"])})
        assert _active(lint_file(p, man), "sync")

    def test_version_mismatch_raises(self, tmp_path):
        bad = tmp_path / "m.json"
        bad.write_text(json.dumps({"version": "tessalint-manifest-v0", "rules": {}}))
        with pytest.raises(ValueError, match="version"):
            Manifest.load(bad)

    def test_repo_manifest_loads_and_names_known_rules(self):
        man = Manifest.load(DEFAULT_MANIFEST_PATH)
        assert man.rules, "repo manifest must scope at least one rule"
        for rule in man.rules:
            assert rule in ALL_RULES
        assert MANIFEST_VERSION == "tessalint-manifest-v1"


# --------------------------------------------------------------------------- #
# JSON schema / report round-trip
# --------------------------------------------------------------------------- #
class TestReportSchema:
    def test_finding_round_trip(self):
        f = Finding(
            "sync",
            "src/x.py",
            10,
            4,
            "message",
            snippet="np.asarray(x)",
            hint="do not",
            severity="P1",
            suppressed=True,
            suppress_reason="because",
            end_line=12,
        )
        assert Finding.from_dict(f.to_dict()) == f

    def test_report_shape(self, tmp_path):
        src = _JAX_PRELUDE + "def f(x: jax.Array):\n    return np.asarray(x)\n"
        p = tmp_path / "mod.py"
        p.write_text(src)
        man = Manifest({"sync": RuleConfig(include=["*.py"])})
        rep, findings = run_paths([p], manifest=man)
        assert rep["version"] == JSON_VERSION
        assert rep["files_scanned"] == 1
        assert rep["counts"]["sync"] == len(rep["findings"]) > 0
        assert rep["suppressed_count"] == 0
        round_tripped = [Finding.from_dict(d) for d in rep["findings"]]
        assert round_tripped == [f for f in findings if not f.suppressed]

    def test_cli_json_output(self, tmp_path, capsys):
        src = _JAX_PRELUDE + "def f(x: jax.Array):\n    return np.asarray(x)\n"
        p = tmp_path / "mod.py"
        p.write_text(src)
        man = tmp_path / "m.json"
        man.write_text(
            json.dumps(
                {
                    "version": MANIFEST_VERSION,
                    "rules": {"sync": {"include": ["*.py"]}},
                }
            )
        )
        rc = cli_main([str(p), "--format", "json", "--manifest", str(man)])
        rep = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert rep["version"] == JSON_VERSION
        assert [f["rule"] for f in rep["findings"]] == ["sync"]

    def test_cli_exit_codes(self, tmp_path, capsys):
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert cli_main([str(clean)]) == 0
        capsys.readouterr()
        assert cli_main([str(clean), "--rules", "nosuchrule"]) == 2


# --------------------------------------------------------------------------- #
# The committed tree lints clean (the CI lane's gate)
# --------------------------------------------------------------------------- #
class TestRealTree:
    def test_src_lints_clean_with_sanctioned_suppressions(self):
        rep, findings = run_paths([REPO_ROOT / "src"])
        assert rep["findings"] == [], [f.format_text() for f in findings if not f.suppressed]
        # the sanctioned readouts exist and are pragma'd, not silent
        assert rep["suppressed_count"] >= 5
        # the suite genuinely exercises >= 5 distinct rules
        assert len(rep["rules"]) >= 5

    def test_deleting_the_fused_readout_pragma_fails_the_lint(self, tmp_path):
        real = (REPO_ROOT / "src" / "repro" / "core" / "fused.py").read_text()
        assert "# tessalint: sync-ok(THE one sanctioned readout" in real
        stripped = []
        for line in real.splitlines(keepends=True):
            if "# tessalint: sync-ok(THE one sanctioned readout" in line:
                line = line.split("  # tessalint:")[0] + "\n"
            stripped.append(line)
        d = tmp_path / "src" / "repro" / "core"
        d.mkdir(parents=True)
        p = d / "fused.py"
        p.write_text("".join(stripped))
        findings = lint_file(p, Manifest.load(DEFAULT_MANIFEST_PATH))
        live = [f for f in findings if not f.suppressed and f.rule == "sync"]
        assert live, "the un-pragma'd device_get readout must be flagged"
        assert any("device_get" in f.message for f in live)

    def test_tools_package_lints_itself_quietly(self):
        # the linter's own tree has no device code; running it must not crash
        rep, _ = run_paths([REPO_ROOT / "tools"])
        assert rep["findings"] == []
