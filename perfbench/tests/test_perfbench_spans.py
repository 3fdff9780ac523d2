"""The program's spans and counter in the cells' traced runs, on the CPU
at the small size of :mod:`tiny`, and the readers of the per-layer
metrics built on them (:mod:`perfbench.spans`)."""
import json
import os
import types

import pytest

from perfbench import harness, spans
from perfbench.tests import tiny

SWEEP = "sweep.islands3"
KERNEL = "cosim.islands3-pid"
SCAN = "cosim.paper2-faults"
CELLS = [SWEEP, KERNEL, SCAN]
COSIM = [KERNEL, SCAN]

# span -> the span that encloses it, in each kind's tree
TREE = {
    "grid_sweep": {"sweep_chunk": "grid_sweep",
                   "sweep_decode": "sweep_chunk",
                   "sweep_device_call": "sweep_chunk",
                   "sweep_front": "grid_sweep"},
    "closed_loop_score": {"cosim_build": "closed_loop_score",
                          "cosim_prepare": "closed_loop_score",
                          "cosim_tick_loop": "closed_loop_score",
                          "cosim_percentiles": "closed_loop_score"},
}
ROOT = {SWEEP: "grid_sweep", KERNEL: "closed_loop_score",
        SCAN: "closed_loop_score"}
NEW_METRICS = ["sweep_decode_ms_per_mpoint",
               "sweep_device_call_ms_per_mpoint",
               "sweep_front_ms_per_mpoint",
               "cosim_build_ms_per_ranking",
               "cosim_prepare_ms_per_ranking",
               "cosim_percentiles_ms_per_ranking",
               "cosim_tick_loop_builds_per_ranking",
               "cosim_gc_full_ms_per_ranking"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One traced run per cell: its result, the ring after it, and each
    ranking's ``elapsed_wall_s`` beside its last ``cosim_tick_loop``
    span."""
    from perfbench.kinds import cosim
    from repro.sim.observe import get_profiler
    root = tiny.make(str(tmp_path_factory.mktemp("tiny")))
    walls = []
    orig = cosim.Workload.job

    def job(self, i):
        info = orig(self, i)
        loop = [s for s in get_profiler().spans()
                if s.name == "cosim_tick_loop"][-1]
        walls.append((info["engine_s"], loop.seconds))
        return info

    mp = pytest.MonkeyPatch()
    mp.setattr(cosim.Workload, "job", job)
    try:
        out = {}
        for cell in CELLS:
            walls.clear()
            res = tiny.run(root, cell, trace=True)
            out[cell] = types.SimpleNamespace(
                result=res, ring=get_profiler().spans(), walls=list(walls))
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_each_kind_records_its_span_tree(runs, cell):
    r = runs[cell]
    root = ROOT[cell]
    by_seq = {s.seq: s for s in r.ring}
    roots = [s for s in r.ring if s.name == root and s.parent is None]
    assert len(roots) >= r.result["attempted"] + 1      # window + traced
    last = roots[-1]
    seen = set()
    for s in r.ring:
        if s.name not in TREE[root] or s.start_ns < last.start_ns:
            continue
        parent = by_seq[s.parent]
        assert parent.name == TREE[root][s.name], (s.name, parent.name)
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
        seen.add(s.name)
    assert seen == set(TREE[root])


@pytest.mark.parametrize("cell", COSIM)
def test_tick_loop_builds_sit_in_the_ranking(runs, cell):
    r = runs[cell]
    by_seq = {s.seq: s for s in r.ring}
    ev = [s for s in r.ring if s.name == "tick_loop_builds"][-1]
    # the scan is built while the run prepares; the kernel in its loop
    want = "cosim_tick_loop" if cell == KERNEL else "cosim_prepare"
    assert ev.count == 1 and by_seq[ev.parent].name == want


@pytest.mark.parametrize("cell", COSIM)
def test_elapsed_wall_is_the_tick_loop_span(runs, cell):
    walls = runs[cell].walls
    assert walls
    for engine_s, span_s in walls:
        assert engine_s == span_s


@pytest.mark.parametrize("cell", CELLS)
def test_every_new_reader_reports_a_number(runs, cell):
    with open(os.path.join(harness.REPO_ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]
                  if m["name"] in NEW_METRICS
                  and cell in m["workloads"]]
    assert len(listed) == (3 if cell == SWEEP else 5)
    m = runs[cell].result["metrics"]
    for name in listed:
        assert name in m, name
        assert m[name]["value"] >= 0.0


@pytest.mark.parametrize("cell", COSIM)
def test_one_tick_loop_build_per_ranking(runs, cell):
    m = runs[cell].result["metrics"]
    assert m["cosim_tick_loop_builds_per_ranking"]["value"] == 1.0


def test_chunk_parts_add_up_to_the_chunk(runs):
    """Decode and the device call make up each chunk's evaluation."""
    r = runs[SWEEP]
    by_seq = {s.seq: s for s in r.ring}
    parts = {}
    for s in r.ring:
        if s.name in ("sweep_decode", "sweep_device_call") \
                and by_seq.get(s.parent, s).name == "sweep_chunk":
            parts[s.parent] = parts.get(s.parent, 0.0) + s.seconds
    assert parts
    for seq, inside in parts.items():
        assert inside <= by_seq[seq].seconds


# -- the window's selection, on a ring built here -----------------------


def _ring(monkeypatch, capacity, jobs, traced=1, before=0):
    """A recorder whose ring holds ``capacity`` entries, after ``before``
    spans and ``jobs + traced`` rankings and sweeps, each with every
    span and the counter the readers look for."""
    from repro.sim import observe
    monkeypatch.setattr(observe, "RING_CAPACITY", capacity)
    prof = observe.Profiler()
    for _ in range(before):
        with observe.profiled("earlier", prof):
            pass
    for _ in range(jobs + traced):
        with observe.profiled("grid_sweep", prof):
            with observe.profiled("sweep_chunk", prof):
                for name in ("sweep_decode", "sweep_device_call"):
                    with observe.profiled(name, prof):
                        pass
            with observe.profiled("sweep_front", prof):
                pass
        with observe.profiled("closed_loop_score", prof):
            for name in ("cosim_build", "cosim_prepare"):
                with observe.profiled(name, prof):
                    pass
            prof.count("tick_loop_builds")
            for name in ("cosim_tick_loop", "cosim_percentiles"):
                with observe.profiled(name, prof):
                    pass
    monkeypatch.setattr(observe, "get_profiler", lambda: prof)
    return types.SimpleNamespace(
        jobs=[{"work": 1_000_000}] * jobs, traced=[{}] * traced)


def _read(name, ctx):
    return harness.load_reader(harness.REPO_ROOT, name).read(ctx)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_whole_window_reads_a_number(monkeypatch, name):
    ctx = _ring(monkeypatch, 4096, jobs=3, before=50)
    v = _read(name, ctx)
    assert v is not None and v >= 0.0
    if name == "cosim_tick_loop_builds_per_ranking":
        assert v == 1.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_ring_that_dropped_window_spans_reads_nothing(monkeypatch, name):
    # 3 window jobs and 1 traced, 11 entries each: the first job's spans
    # of both kinds fall off a ring of 36
    ctx = _ring(monkeypatch, 36, jobs=3)
    assert _read(name, ctx) is None


def test_spans_dropped_before_the_window_do_not_matter(monkeypatch):
    ctx = _ring(monkeypatch, 80, jobs=3, before=40)
    jobs = spans.window(ctx, "closed_loop_score")
    assert jobs is not None and len(jobs) == 3
    assert spans.counted(jobs, "tick_loop_builds") == 3


def test_fewer_roots_than_jobs_read_nothing(monkeypatch):
    ctx = _ring(monkeypatch, 4096, jobs=2)
    ctx.jobs = ctx.jobs * 2
    assert spans.window(ctx, "grid_sweep") is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    from repro.sim import observe
    monkeypatch.setattr(observe, "get_profiler",
                        lambda: types.SimpleNamespace(phases={}))
    ctx = types.SimpleNamespace(jobs=[{"work": 1}], traced=[{}])
    for name in NEW_METRICS:
        assert _read(name, ctx) is None
