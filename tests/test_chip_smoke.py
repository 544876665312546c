"""The chip entry points that need no chip: the compile-cache location,
``chip_smoke.py``'s refusal to run without a TPU, and its phases at a
small cluster size on the CPU devices the suite forces."""

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself; nothing else is set


def test_compile_cache_fixed_in_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""  # no phase ran, no result line
    assert "no TPU" in proc.stderr


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclass resolves names through it
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


@pytest.mark.parametrize(
    "phase, verdict",
    [
        ("main", "matching_cost equal to host scipy on 3/3 rounds"),
        ("tie-break", "plans bit-identical to host scipy on 11/11 rounds"),
        ("sharded", "bit-identical on 3 rounds"),
    ],
)
def test_chip_smoke_phases_small(chip_smoke, phase, verdict, capsys):
    """Each phase's checks pass on a small saturated cluster: fused rounds
    against the host planner, tie-break plan parity, and four shards
    against one (the suite forces 8 CPU devices)."""
    cs = chip_smoke
    if phase == "main":
        cs.main_phase(0, cs.CompileClock(), nodes=8, jobs=64, rounds=4)
    elif phase == "tie-break":
        cs.tie_break_phase(0)
    else:
        cs.sharded_phase(0, 4, nodes=8, jobs=64, rounds=4)
    assert verdict in capsys.readouterr().out
