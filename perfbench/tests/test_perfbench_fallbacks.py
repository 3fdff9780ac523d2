"""The reader of ``cosim_percentile_fallbacks_per_ranking``: the
program's ``percentile_host_fallbacks`` counter over the window's
rankings, on rings built here and on a real ranking at a small size."""
import types

import numpy as np
import pytest

from perfbench import harness

METRIC = "cosim_percentile_fallbacks_per_ranking"
COUNTER = "percentile_host_fallbacks"


def _ring(monkeypatch, fallbacks, traced=()):
    """A recorder holding one ranking per entry of ``fallbacks``, then
    the traced ones: each counts its entry, or counts nothing where the
    entry is ``None``, as a program without the counter."""
    from repro.sim import observe
    prof = observe.Profiler()
    for n in list(fallbacks) + list(traced):
        with observe.profiled("closed_loop_score", prof):
            with observe.profiled("cosim_percentiles", prof):
                if n is not None:
                    prof.count(COUNTER, n)
    monkeypatch.setattr(observe, "get_profiler", lambda: prof)
    return types.SimpleNamespace(jobs=[{"work": 1}] * len(fallbacks),
                                 traced=[{}] * len(traced))


def _read(ctx):
    return harness.load_reader(harness.REPO_ROOT, METRIC).read(ctx)


@pytest.mark.parametrize("fallbacks,want", [([2, 0, 7], 3.0),
                                            ([0, 0, 0], 0.0),
                                            ([None, None], None)])
def test_the_reader_averages_the_counter(monkeypatch, fallbacks, want):
    """Counts per ranking; 0 for a program that never falls back;
    nothing for a program that has no such counter."""
    traced = [None] if want is None else [0]
    assert _read(_ring(monkeypatch, fallbacks, traced)) == want


def test_the_traced_rankings_do_not_count(monkeypatch):
    assert _read(_ring(monkeypatch, [1, 1], traced=[9, 9])) == 1.0


def test_a_ranking_on_the_device_counts_its_fallbacks(monkeypatch):
    """A real ``closed_loop_score`` ranking on the ``jax`` backend books
    the counter once, with the designs it recomputed on the host: here
    none, so the metric reads 0."""
    from repro.core.dse import closed_loop_score, grid_sweep
    from repro.core.perfmodel import AccelWorkload, SoCPerfModel
    from repro.sim import diurnal_trace, observe
    prof = observe.Profiler()
    monkeypatch.setattr(observe, "_GLOBAL_PROFILER", prof)
    m = SoCPerfModel()
    res = grid_sweep(m, [AccelWorkload("dfadd", 9.22, 0.9),
                         AccelWorkload("dfmul", 8.70, 1.1)],
                     ks=(1, 2), acc_rates=(0.6, 1.0), noc_rates=(1.0,),
                     n_tg=2)
    tr = diurnal_trace(1500.0, 120, 2, dt=1e-3, depth=0.4, seed=3)
    for _ in range(2):
        s = closed_loop_score(res, tr, model=m, req_mb=0.002,
                              indices=res.topk_indices(6), backend="jax")
    assert np.isfinite(s.p99_latency_s).all()
    events = [e for e in prof.spans() if e.name == COUNTER]
    assert [e.count for e in events] == [0, 0]
    ctx = types.SimpleNamespace(jobs=[{"work": 1}], traced=[{}])
    monkeypatch.setattr(observe, "get_profiler", lambda: prof)
    assert _read(ctx) == 0.0
