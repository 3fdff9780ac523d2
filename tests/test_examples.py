"""The SoC examples run to their end.

Each case runs one mode of ``examples/dse_sweep.py`` or
``examples/closed_loop.py`` in a subprocess on the CPU and checks that it
exits 0 and prints the line that follows its own acceptance asserts (the
``✓`` line where the example prints one).  The examples are what the
README sends users to, and they assert the paper's claims themselves.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

CASES = [
    ("dse_sweep.py", (), "DFS energy policy (batched ladder sweep)"),
    ("dse_sweep.py", ("--accel", "dfmul"),
     "DFS energy policy (batched ladder sweep)"),
    ("dse_sweep.py", ("--accel", "dfsin", "--tg", "0"),
     "DFS energy policy (batched ladder sweep)"),
    ("dse_sweep.py", ("--independent-islands",),
     "per-island Pareto points strictly dominate"),
    ("dse_sweep.py", ("--tech-node", "16"),
     "islands the physical model re-frequencies"),
    ("dse_sweep.py", ("--tech-node", "8", "--tech-variant", "cons"),
     "islands the physical model re-frequencies"),
    ("closed_loop.py", ("--dse",),
     "acceptance: >=10% energy/request saving at matched p99 ✓"),
    ("closed_loop.py", ("--pipeline",),
     "acceptance: lb+dfs < dfs-only and < lb-only energy/request "
     "at matched p99 ✓"),
    ("closed_loop.py", ("--faults",),
     "acceptance: replica kill mid-surge survives with <1% drops at "
     "bounded p99, DFS still saving energy ✓"),
    ("closed_loop.py", ("--observe",),
     "zero-perturbation: observed run == unobserved run, bit for bit ✓"),
]


@pytest.mark.parametrize(
    "script,args,expect", CASES,
    ids=[" ".join((s,) + a) for s, a, _ in CASES])
def test_example_runs_to_its_acceptance(script, args, expect):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert expect in proc.stdout, proc.stdout[-2000:]
