"""The analyzer analyzed: fixture snippets per rule (positive,
negative, noqa), baseline round-trip, the CLI self-check against the
committed baseline, and seeded mutation tests proving each rule still
fires on a known-bad snippet — including re-introducing PR 8's
dt-missing-from-the-jit-cache-key bug into the real ``sim/batch.py``
source, which RPR002 must catch.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import (Finding, analyze_paths, load_baseline,
                            save_baseline)
from repro.analysis.findings import (extract_comments, fingerprint,
                                     parse_noqa)
from repro.analysis.rules import RULES, get_rules

ROOT = Path(__file__).resolve().parents[1]
BATCH_SRC = ROOT / "src" / "repro" / "sim" / "batch.py"


def run_on(tmp_path, sources, rules=None):
    """Write {relpath: source} under tmp_path and analyze them all."""
    paths = []
    for rel, src in sources.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
        paths.append(f)
    return analyze_paths(paths, root=tmp_path,
                         rules=get_rules(rules) if rules else None)


def by_rule(report, rule):
    return [f for f in report.new if f.rule == rule]


# ---------------------------------------------------------------- RPR001
RPR001_POS = """\
import jax
import jax.numpy as jnp

def step(x):
    if x > 0:
        return x
    return float(x) * jnp.ones(())

fast = jax.jit(step)
"""

RPR001_NEG = """\
import jax
import jax.numpy as jnp

def step(x, cfg, n: int, *, gain):
    if cfg.mode == "fast":          # config object: static
        x = x * gain                # kw-only: static
    if n > 2:                       # int-annotated: static
        x = x + 1
    if x.shape[0] > 1:              # shape read: static
        x = x.sum()
    if x is None:                   # identity check: static
        return jnp.zeros(())
    return jnp.where(x > 0, x, -x)  # traced branch done right

fast = jax.jit(step)
"""


def test_rpr001_fires_on_traced_branch_and_coercion(tmp_path):
    rep = run_on(tmp_path, {"snippet.py": RPR001_POS}, rules=["RPR001"])
    msgs = [f.message for f in by_rule(rep, "RPR001")]
    assert any("`if` on a traced value" in m for m in msgs)
    assert any("float() coerces" in m for m in msgs)


def test_rpr001_quiet_on_static_idioms(tmp_path):
    rep = run_on(tmp_path, {"snippet.py": RPR001_NEG}, rules=["RPR001"])
    assert by_rule(rep, "RPR001") == []


def test_rpr001_scan_body_reached_through_call_graph(tmp_path):
    src = (
        "from jax import lax\n"
        "def helper(c):\n"
        "    if c:\n"
        "        return c\n"
        "    return -c\n"
        "def step(carry, x):\n"
        "    return helper(carry), x\n"
        "def run(xs):\n"
        "    return lax.scan(step, 0.0, xs)\n")
    rep = run_on(tmp_path, {"snippet.py": src}, rules=["RPR001"])
    hits = by_rule(rep, "RPR001")
    assert len(hits) == 1 and "helper" in hits[0].message


def test_rpr001_traced_marker_opts_a_closure_in(tmp_path):
    body = ("def outer():\n"
            "    def inner(x):{marker}\n"
            "        if x > 0:\n"
            "            return x\n"
            "        return -x\n"
            "    return inner\n")
    quiet = run_on(tmp_path, {"s.py": body.format(marker="")},
                   rules=["RPR001"])
    assert by_rule(quiet, "RPR001") == []
    loud = run_on(tmp_path,
                  {"s.py": body.format(marker="  # repro: traced")},
                  rules=["RPR001"])
    assert len(by_rule(loud, "RPR001")) == 1


def test_rpr001_noqa_suppresses_with_justification(tmp_path):
    src = RPR001_POS.replace(
        "    if x > 0:",
        "    if x > 0:  # repro: noqa[RPR001] debug-only host branch")
    rep = run_on(tmp_path, {"snippet.py": src}, rules=["RPR001"])
    assert not any("`if` on a traced value" in f.message
                   for f in by_rule(rep, "RPR001"))
    sup = [f for f in rep.suppressed if f.rule == "RPR001"]
    assert sup and sup[0].justification == "debug-only host branch"


def test_noqa_without_justification_is_rpr000(tmp_path):
    src = RPR001_POS.replace(
        "    if x > 0:", "    if x > 0:  # repro: noqa[RPR001]")
    rep = run_on(tmp_path, {"snippet.py": src}, rules=["RPR001"])
    assert any(f.rule == "RPR000" and "justification" in f.message
               for f in rep.new)


def test_noqa_in_docstring_is_inert():
    comments = extract_comments(
        'def f():\n    """# repro: noqa[RPR001] not a comment"""\n'
        "    return 1  # repro: noqa[RPR003] real comment\n")
    assert list(comments) == [3]
    assert parse_noqa(comments[3]) == ({"RPR003"}, "real comment")


# ---------------------------------------------------------------- RPR002
CACHE_SNIPPET = """\
import jax

class Eng:
    def __init__(self):
        self._cache = {{}}

    def _cached_fn(self, sig, build):
        fn = self._cache.get(sig)
        if fn is None:
            fn = build()
            self._cache[sig] = fn
            while len(self._cache) > 4:
                self._cache.pop(next(iter(self._cache)))
        return fn

    def run(self, trace):
        T = trace.ticks
        dt = trace.dt

        def inner(x):
            return x * dt + T

        def build():
            return jax.jit(inner)

        sig = {sig}
        return self._cached_fn(sig, build)
"""


def test_rpr002_flags_param_derived_value_missing_from_key(tmp_path):
    rep = run_on(tmp_path,
                 {"s.py": CACHE_SNIPPET.format(sig='("scan", T)')},
                 rules=["RPR002"])
    hits = by_rule(rep, "RPR002")
    assert len(hits) == 1 and "`dt`" in hits[0].message


def test_rpr002_quiet_when_key_is_complete(tmp_path):
    rep = run_on(tmp_path,
                 {"s.py": CACHE_SNIPPET.format(sig='("scan", T, dt)')},
                 rules=["RPR002"])
    assert by_rule(rep, "RPR002") == []


def test_rpr002_helper_call_counts_as_keying_its_args(tmp_path):
    src = CACHE_SNIPPET.format(sig="self._sig(T=T, dt=dt)") + (
        "\n    def _sig(self, *, T, dt):\n"
        "        return (\"scan\", T, dt)\n")
    rep = run_on(tmp_path, {"s.py": src}, rules=["RPR002"])
    assert by_rule(rep, "RPR002") == []


def test_rpr002_lossy_derivation_does_not_count_as_keyed(tmp_path):
    # keying f(dt) is not keying dt: the derived value can collapse
    # distinct dt (the PR 8 bug shape: deadline_ticks=None erased dt)
    src = CACHE_SNIPPET.format(sig='("scan", T, ticks2)').replace(
        "        dt = trace.dt\n",
        "        dt = trace.dt\n        ticks2 = dt / 2 if T else None\n")
    rep = run_on(tmp_path, {"s.py": src}, rules=["RPR002"])
    assert any("`dt`" in f.message for f in by_rule(rep, "RPR002"))


def test_rpr002_mutation_real_batch_missing_dt_fires(tmp_path):
    """Re-introduce PR 8's dt-cache-collision bug into the real source:
    drop dt from the _scan_cache_sig call — RPR002 must catch it."""
    src = BATCH_SRC.read_text()
    assert "sig = self._scan_cache_sig(T=T, ci=ci, dt=dt," in src
    mut = src.replace("sig = self._scan_cache_sig(T=T, ci=ci, dt=dt,",
                      "sig = self._scan_cache_sig(T=T, ci=ci,")
    mut = mut.replace("def _scan_cache_sig(self, *, T, ci, dt, B,",
                      "def _scan_cache_sig(self, *, T, ci, dt=0.0, B=0,")
    rep = run_on(tmp_path, {"sim/batch.py": mut}, rules=["RPR002"])
    assert any("`dt`" in f.message for f in by_rule(rep, "RPR002"))


def test_rpr002_unmutated_batch_is_clean(tmp_path):
    rep = run_on(tmp_path, {"sim/batch.py": BATCH_SRC.read_text()},
                 rules=["RPR002"])
    assert by_rule(rep, "RPR002") == []


# ---------------------------------------------------------------- RPR003
def test_rpr003_unbounded_shapes_fire(tmp_path):
    src = (
        "import functools\n"
        "from functools import lru_cache\n"
        "_CACHE = {}\n"
        "def put(k, v):\n"
        "    _CACHE[k] = v\n"
        "@lru_cache(maxsize=None)\n"
        "def slow(x):\n"
        "    return x\n"
        "@functools.cache\n"
        "def slower(x):\n"
        "    return x\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self.memo = {}\n"
        "    def get(self, k):\n"
        "        if k not in self.memo:\n"
        "            self.memo[k] = k * 2\n"
        "        return self.memo[k]\n")
    rep = run_on(tmp_path, {"s.py": src}, rules=["RPR003"])
    msgs = " | ".join(f.message for f in by_rule(rep, "RPR003"))
    assert "_CACHE" in msgs
    assert "maxsize=None" in msgs
    assert "functools.cache" in msgs
    assert "self.memo" in msgs


def test_rpr003_bounded_shapes_pass(tmp_path):
    src = (
        "from functools import lru_cache\n"
        "from collections import OrderedDict\n"
        "_CACHE = {}\n"
        "def put(k, v):\n"
        "    _CACHE[k] = v\n"
        "    while len(_CACHE) > 8:\n"
        "        _CACHE.pop(next(iter(_CACHE)))\n"
        "@lru_cache(maxsize=32)\n"
        "def slow(x):\n"
        "    return x\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self.state = {}\n"
        "    def put(self, k, v):\n"
        "        self.state[k] = v   # plain bookkeeping, not a memo\n")
    rep = run_on(tmp_path, {"s.py": src}, rules=["RPR003"])
    assert by_rule(rep, "RPR003") == []


# ---------------------------------------------------------------- RPR004
def test_rpr004_f32_in_reference_scope_fires(tmp_path):
    src = ("import numpy as np\n"
           "def run():\n"
           "    return np.zeros(3, dtype=np.float32)\n")
    rep = run_on(tmp_path, {"sim/engine.py": src}, rules=["RPR004"])
    assert len(by_rule(rep, "RPR004")) == 1
    # same code outside the declared reference set: fine
    rep2 = run_on(tmp_path, {"other.py": src}, rules=["RPR004"])
    assert by_rule(rep2, "RPR004") == []


def test_rpr004_direct_f64_on_jax_path_fires(tmp_path):
    src = ("import numpy as np\n"
           "import jax.numpy as jnp\n"
           "def up(x):\n"
           "    return jnp.asarray(x, dtype=jnp.float64)\n"
           "def stage(x):\n"
           "    return np.asarray(x, dtype=np.float64)  # host: fine\n")
    rep = run_on(tmp_path, {"other.py": src}, rules=["RPR004"])
    hits = by_rule(rep, "RPR004")
    assert len(hits) == 1 and hits[0].line == 4


# ---------------------------------------------------------------- RPR005
PALLAS_BAD = """\
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl

def make(x):
    table = jnp.arange(4.0)

    def kernel(ref, o_ref):
        v = ref[...]
        if v[0] > 0:
            o_ref[...] = v
        o_ref[...] = v + np.exp(1.0) + table[0]

    return pl.pallas_call(kernel, out_shape=x)
"""

PALLAS_OK = """\
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl
import functools

def _kernel(*refs, n, extra_bool):
    dtp = refs[0].dtype
    if np.issubdtype(dtp, np.bool_):      # static metadata: fine
        pass
    for isb, ref in zip(extra_bool, refs[1:]):
        v = ref[...]
        o = (v > 0.5) if isb else v       # static selector: fine
        refs[-1][...] = jnp.where(o > 0, o, v)

def make(x, n):
    kernel = functools.partial(_kernel, n=n, extra_bool=(True,))
    return pl.pallas_call(kernel, out_shape=x)
"""


def test_rpr005_kernel_violations_fire(tmp_path):
    rep = run_on(tmp_path, {"s.py": PALLAS_BAD}, rules=["RPR005"])
    msgs = " | ".join(f.message for f in by_rule(rep, "RPR005"))
    assert "closes over array-valued `table`" in msgs
    assert "np.exp" in msgs
    assert "`if` on a traced value" in msgs


def test_rpr005_idiomatic_kernel_via_partial_passes(tmp_path):
    rep = run_on(tmp_path, {"s.py": PALLAS_OK}, rules=["RPR005"])
    assert by_rule(rep, "RPR005") == []


# ---------------------------------------------------------------- RPR006
FAKE_ENGINE = """\
class SimEngine:
    def __init__(self, platform, *, config=None, controller=None,
                 balancer=None, faults=None, slo=None, supervisor=None,
                 tech=None, observe=None):
        pass
"""

FAKE_BATCH = """\
class BatchSimEngine:
    def __init__(self, platform, *, config=None, controller=None,
                 balancer=None, backend="numpy", faults=None, slo=None,
                 observe=None, devices=None, tech=None):
        pass

    def _run_pallas(self):
        raise NotImplementedError("no fault schedules here")
        raise NotImplementedError("no SLO semantics here")
        raise NotImplementedError("no load balancer here")
        raise NotImplementedError("no observer plane here")
"""

FAKE_DSE = """\
def closed_loop_score(result, trace, *, model, backend="numpy",
                      flows=None, balancer_factory=None,
                      fault_schedule=None, slo=None, observe=None,
                      devices=None, tech=None):
    pass


def grid_sweep(model, *, devices=None, tech_node=None,
               tech_variant=None):
    pass
"""


def _fake_surfaces():
    return {"sim/engine.py": FAKE_ENGINE, "sim/batch.py": FAKE_BATCH,
            "core/dse.py": FAKE_DSE}


def test_rpr006_parity_matrix_green_on_full_surfaces(tmp_path):
    rep = run_on(tmp_path, _fake_surfaces(), rules=["RPR006"])
    assert by_rule(rep, "RPR006") == []


def test_rpr006_desynced_surface_fires(tmp_path):
    srcs = _fake_surfaces()
    srcs["sim/engine.py"] = FAKE_ENGINE.replace("observe=None", "obs=None")
    rep = run_on(tmp_path, srcs, rules=["RPR006"])
    hits = by_rule(rep, "RPR006")
    assert any("must accept knob `observe`" in f.message for f in hits)


def test_rpr006_undeclared_knob_growth_fires(tmp_path):
    srcs = _fake_surfaces()
    srcs["sim/engine.py"] = FAKE_ENGINE.replace(
        "observe=None):", "observe=None, backend=None):")
    rep = run_on(tmp_path, srcs, rules=["RPR006"])
    assert any("declares absent" in f.message
               for f in by_rule(rep, "RPR006"))


def test_rpr006_missing_refusal_fires(tmp_path):
    srcs = _fake_surfaces()
    srcs["sim/batch.py"] = FAKE_BATCH.replace(
        '        raise NotImplementedError("no observer plane here")\n',
        "")
    rep = run_on(tmp_path, srcs, rules=["RPR006"])
    assert any("observer plane" in f.message
               for f in by_rule(rep, "RPR006"))


# ------------------------------------------------- fingerprints / baseline
def test_fingerprint_stable_across_line_shifts(tmp_path):
    rep1 = run_on(tmp_path, {"a.py": RPR001_POS}, rules=["RPR001"])
    shifted = "# a leading comment\nX = 1\n\n" + RPR001_POS
    rep2 = run_on(tmp_path, {"a.py": shifted}, rules=["RPR001"])
    fp1 = sorted(f.fingerprint for f in rep1.new)
    fp2 = sorted(f.fingerprint for f in rep2.new)
    assert fp1 == fp2 and all(fp1)


def test_baseline_round_trip(tmp_path):
    rep = run_on(tmp_path, {"a.py": RPR001_POS}, rules=["RPR001"])
    assert rep.new and rep.exit_code == 1
    bl = tmp_path / "baseline.json"
    save_baseline(bl, rep.findings)
    accepted = load_baseline(bl)
    assert accepted == {f.fingerprint for f in rep.new}
    rep2 = analyze_paths([tmp_path / "a.py"], root=tmp_path,
                         baseline=accepted, rules=get_rules(["RPR001"]))
    assert rep2.new == [] and rep2.baselined and rep2.exit_code == 0


def test_baseline_version_mismatch_raises(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError):
        load_baseline(bl)


# ------------------------------------------------------------ CLI / gate
def _cli(*args, cwd=ROOT):
    import os
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    return subprocess.run([sys.executable, "-m", "repro.analysis", *args],
                          cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_cli_self_check_repo_is_clean_against_committed_baseline():
    """`python -m repro.analysis src/repro` exits 0 for the repo as
    committed — the CI gate invariant."""
    proc = _cli("src/repro")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_format():
    proc = _cli("--format", "json", "src/repro")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["counts"]["new"] == 0
    assert doc["modules"] > 50


def test_cli_exits_nonzero_on_new_finding(tmp_path):
    (tmp_path / "bad.py").write_text(RPR001_POS)
    proc = _cli(str(tmp_path / "bad.py"), "--baseline", "none",
                cwd=ROOT)
    assert proc.returncode == 1
    assert "RPR001" in proc.stdout


def test_cli_changed_only_in_fresh_git_repo(tmp_path):
    try:
        subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True,
                       capture_output=True, timeout=60)
    except (OSError, subprocess.SubprocessError):
        pytest.skip("git unavailable")
    (tmp_path / "bad.py").write_text(RPR001_POS)
    proc = _cli("--changed-only", "--baseline", "none", cwd=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "bad.py" in proc.stdout


# --------------------------------------------------- scan cache signature
def test_scan_cache_sig_enumerates_every_field():
    """SCAN_SIG_FIELDS is the authoritative slot list: the helper's
    tuple must have exactly these arity/slots, with the raw scalars in
    the positions the names claim."""
    from repro.core.perfmodel import AccelWorkload, SoCPerfModel
    from repro.sim import BatchSimEngine, BatchSimPlatform, SimPlatform
    from repro.sim.batch import SCAN_SIG_FIELDS

    m = SoCPerfModel()
    pos = [(0, 0), (0, 1), (1, 1), (2, 1)]
    wls = [AccelWorkload("dfmul", 8.70, 1.1, replication=8) for _ in pos]
    plat = SimPlatform.build(m, wls, pos)
    eng = BatchSimEngine(BatchSimPlatform.stack([plat]))

    fault_key = ("fk",)
    sig = eng._scan_cache_sig(T=64, ci=4, dt=1e-3, B=1, D=1,
                              arrivals_ndim=2, fault_key=fault_key,
                              plan={"kind": "none"}, slo=None)
    assert len(sig) == len(SCAN_SIG_FIELDS) == 14
    ix = {name: i for i, name in enumerate(SCAN_SIG_FIELDS)}
    assert sig[ix["tag"]] == "scan"
    assert sig[ix["T"]] == 64
    assert sig[ix["ci"]] == 4
    assert sig[ix["dt"]] == 1e-3
    assert sig[ix["B"]] == 1
    assert sig[ix["D"]] == 1
    assert sig[ix["arrivals_ndim"]] == 2
    assert sig[ix["fault_key"]] is fault_key
    assert sig[ix["policy_digest"]] == ("none",)
    assert sig[ix["balancer_digest"]] is None
    assert sig[ix["slo"]] is None
    # config / model slots key the scalars that retrace the scan
    cfg = eng.config
    assert sig[ix["config"]] == (cfg.max_queue, cfg.dynamic_contention,
                                 cfg.noc_power_share)
    mdl = sig[ix["model"]]
    assert mdl[0] == m.own_demand and mdl[-1] == plat.n_tg
    # tech slot: engine + controller tech identities (linear proxy here)
    assert sig[ix["tech"]] == (None, None)
    # distinct dt MUST produce a distinct signature (the PR 8 bug)
    sig2 = eng._scan_cache_sig(T=64, ci=4, dt=2e-3, B=1, D=1,
                               arrivals_ndim=2, fault_key=fault_key,
                               plan={"kind": "none"}, slo=None)
    assert sig != sig2


def test_every_rule_module_declares_id_and_summary():
    ids = [m.RULE_ID for m in RULES]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    for m in RULES:
        assert m.RULE_ID.startswith("RPR") and m.SUMMARY
        assert callable(getattr(m, "check", None)) or \
            callable(getattr(m, "check_project", None))
    with pytest.raises(ValueError):
        get_rules(["RPR999"])
