"""The co-simulation cells' ``correct`` on the CPU, at the small size of
:mod:`tiny`: sound runs pass on the Pallas kernel (interpreted here) and
on the scan; the bfloat16 control and each fault the timed path can have
fail."""
import types

import numpy as np
import pytest

from perfbench import compare, harness
from perfbench import reference as ref
from perfbench.tests import tiny

KERNEL = "cosim.islands3-pid"
SCAN = "cosim.paper2-faults"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", [KERNEL, SCAN])
def test_sound_run_is_correct(root, cell):
    out = tiny.run(root, cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["energy_err"]["value"] < 1e-4


@pytest.mark.parametrize("cell", [KERNEL, SCAN])
def test_bfloat16_control_fails(root, cell):
    p = harness.plan(root, cell)
    d = p.kind.Workload(types.SimpleNamespace(cfg=p.cfg, traffic=p.traffic,
                                            seed=7))
    d.tr = dict(d.tr, backend="numpy")       # no kernel needed here
    d.setup()
    arrivals = d.pool[0]
    sim, sc = d.reference(arrivals)
    lsim, lsc = d.reference(arrivals, ref.BF16)
    got = {"completed": lsim["completed"], "energy": lsim["energy"],
           "p99": lsc["p99"], "swaps": lsim["swaps"],
           "drop_rate": lsc["drop_rate"], "order": lsc["order"]}
    judged = compare.judge(compare.cosim_numbers(got, sim, sc, d.faulted),
                           p.limits)
    assert not all(j["ok"] for j in judged.values()), judged
    assert judged["energy_err"]["value"] > 2 * p.limits["energy_err"]


def _break_engine(monkeypatch, fault):
    from repro.core import dse
    from repro.sim import batch
    orig = batch.BatchSimEngine.run
    if fault == "ranking":
        rank = dse._rank_scores

        def swapped(*a, **kw):
            # the best and the worst design trade places
            order = np.array(rank(*a, **kw))
            order[[0, -1]] = order[[-1, 0]]
            return order
        monkeypatch.setattr(dse, "_rank_scores", swapped)
        return

    def run(self, trace):
        if fault == "unchanged":
            # every tick returns the state it was given: nothing arrives,
            # nothing is served
            trace = type(trace)(np.zeros_like(trace.arrivals), trace.dt)
        r = orig(self, trace)
        if fault == "altered":
            r.energy_j = r.energy_j.copy()
            r.energy_j[0] *= 1.02
        elif fault == "swaps":
            # one design's DFS commits miscounted
            r.swaps = np.array(r.swaps).copy()
            r.swaps[0] += 1
        elif fault == "half":
            h = r.n_designs // 2
            for name in ("completed", "energy_j"):
                a = getattr(r, name).copy()
                a[h:] = a[:h].mean()
                setattr(r, name, a)
        return r
    monkeypatch.setattr(batch.BatchSimEngine, "run", run)


@pytest.mark.parametrize("cell", [KERNEL, SCAN])
@pytest.mark.parametrize("fault", ["altered", "half", "unchanged", "swaps",
                                   "ranking"])
def test_a_broken_timed_path_fails(root, monkeypatch, cell, fault):
    _break_engine(monkeypatch, fault)
    out = tiny.run(root, cell)
    assert not out["correct"], out["checks"]


def test_traced_run_reports_host_layers_and_stays_correct(root):
    """On the CPU the trace holds no TPU plane: the device readers find
    nothing and stay silent; the host readers and ``correct`` do not."""
    out = tiny.run(root, SCAN, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["cosim_driver_ms_per_ranking"]["value"] > 0
    assert m["cosim_compile_s_per_ranking"]["value"] >= 0
    assert "tick_scan_roofline" not in m and "device_idle.cosim" not in m
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"


def test_warmup_and_window_share_one_call_site(root, monkeypatch):
    """Warm-up rankings count as set-up; the window's jobs follow them."""
    from perfbench.kinds import cosim
    seen = []
    orig = cosim.Workload.job
    monkeypatch.setattr(cosim.Workload, "job",
                        lambda self, i: seen.append(i) or orig(self, i))
    out = tiny.run(root, KERNEL)
    assert seen[:3] == [0, 1, 2]
    assert out["attempted"] == len(seen) - 2
