"""Run-time monitoring infrastructure (PR 7): the observability plane.

The contracts locked down here:

* **zero perturbation** — simulated numerics are bit-for-bit identical
  with monitoring on or off, on every engine, across control policies and
  fault schedules (the observer only *reads* what ``tick_step`` computed);
* **engine agreement** — the batched NumPy engine's counter plane at B=1
  equals the sequential engine's exactly; the jax backend's counters agree
  within float32-snapshot tolerance;
* **the trace schema** — registered kinds only, monotonic ticks, ring
  bounding, JSONL round-trip;
* **metrics export** — CounterPlane/trace/telemetry -> Prometheus text ->
  parse round-trips, and counter values match the engine's own histories;
* **the level knob** — ``off`` engages nothing, ``counters`` skips
  tracing, ``full`` records both; counters materialize lazily, outside
  the engine wall clock;
* **the span recorder** — phase totals, span nesting, counter events,
  the bounded ring, ``gc_full`` spans, and spans on the clock of the JAX
  profiler's host plane.
"""
import gc
import glob
import time
from functools import partial

import numpy as np
import pytest

from repro.core.dfs import (BatchMemoryBoundPolicy, BatchPIDRatePolicy,
                            PIDRatePolicy, policy_memory_bound)
from repro.core.perfmodel import AccelWorkload, SoCPerfModel
from repro.sim import (LEVELS, TRACE_KINDS, BatchControllerHarness,
                       BatchSimEngine, BatchSimPlatform, ControllerHarness,
                       ControlTrace, CounterPlane, FaultSchedule,
                       MetricsRegistry, Observer, Profiler, SimConfig,
                       SimEngine, SimPlatform, SLOConfig, export_metrics,
                       get_profiler, parse_prometheus_text, poisson_trace,
                       profiled)
from repro.sim.observe import RING_CAPACITY

T = 300
DT = 1e-3


def make_platform() -> SimPlatform:
    m = SoCPerfModel()
    pos = [(r, c) for r in range(4) for c in range(4)
           if (r, c) not in {(1, 0), (0, 0), (0, 3)}][:6]
    wls = [AccelWorkload("dfmul", 8.70, 1.1, replication=8) for _ in pos]
    return SimPlatform.build(m, wls, pos, n_tg=2, req_mb=0.005)


@pytest.fixture(scope="module")
def plat():
    return make_platform()


@pytest.fixture(scope="module")
def trace_():
    return poisson_trace(4000.0, T, 6, dt=DT, seed=11)


def seq_kwargs(plat, policy):
    if policy is None:
        return {}
    pol = (partial(policy_memory_bound, threshold=0.55, low_rate=0.5)
           if policy == "membound" else PIDRatePolicy(target=0.7))
    return dict(controller=ControllerHarness(plat.islands, pol,
                                             queue_guard_ticks=3.0))


def bat_kwargs(bplat, policy):
    if policy is None:
        return {}
    pol = (BatchMemoryBoundPolicy(threshold=0.55, low_rate=0.5)
           if policy == "membound" else BatchPIDRatePolicy(target=0.7))
    return dict(controller=BatchControllerHarness(
        bplat.islands, bplat.rates, pol, tile_names=bplat.names,
        queue_guard_ticks=3.0))


def fault_kwargs(plat, use_faults):
    if not use_faults:
        return {}
    return dict(faults=FaultSchedule().kill_tile(plat.names[2],
                                                 start=80, end=200),
                slo=SLOConfig(deadline_s=0.05, on_kill="respill",
                              max_retries=1))


# ----------------------------------------------------------- perturbation


@pytest.mark.parametrize("policy", [None, "membound", "pid"])
@pytest.mark.parametrize("use_faults", [False, True])
def test_sequential_monitoring_is_zero_perturbation(plat, trace_, policy,
                                                    use_faults):
    """Bit-for-bit: enabling full monitoring must not change a single
    simulated number on the sequential reference engine."""
    cfg = SimConfig(control_interval=25)
    fkw = fault_kwargs(plat, use_faults)
    r_off = SimEngine(plat, config=cfg, **seq_kwargs(plat, policy),
                      **fkw).run(trace_)
    eng = SimEngine(plat, config=cfg, observe="full",
                    **seq_kwargs(plat, policy), **fkw)
    r_on = eng.run(trace_)
    assert r_off.p99_latency_s == r_on.p99_latency_s
    assert r_off.energy_j == r_on.energy_j
    assert r_off.completed == r_on.completed
    assert eng.observer.counters is not None


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_batched_monitoring_is_zero_perturbation(plat, trace_, backend):
    """Same contract on both batched backends, under the PID controller
    with a mid-run tile kill (the hardest numeric path)."""
    cfg = SimConfig(control_interval=25)
    bplat = BatchSimPlatform.stack([plat] * 2)
    fkw = fault_kwargs(plat, True)
    r_off = BatchSimEngine(bplat, config=cfg, backend=backend,
                           **bat_kwargs(bplat, "pid"), **fkw).run(trace_)
    eng = BatchSimEngine(bplat, config=cfg, backend=backend,
                         observe="counters", **bat_kwargs(bplat, "pid"),
                         **fkw)
    r_on = eng.run(trace_)
    assert np.array_equal(r_off.p99_latency_s, r_on.p99_latency_s)
    assert np.array_equal(r_off.energy_j, r_on.energy_j)
    assert np.array_equal(r_off.completed, r_on.completed)
    assert eng.observer.counters is not None


# ------------------------------------------------------- engine agreement


def _planes(plat, trace_, policy, use_faults):
    cfg = SimConfig(control_interval=25)
    fkw = fault_kwargs(plat, use_faults)
    seq = SimEngine(plat, config=cfg, observe="counters",
                    **seq_kwargs(plat, policy), **fkw)
    seq.run(trace_)
    bplat = BatchSimPlatform.stack([plat])
    bat = BatchSimEngine(bplat, config=cfg, backend="numpy",
                         observe="counters", **bat_kwargs(bplat, policy),
                         **fkw)
    bat.run(trace_)
    return seq.observer.counters, bat.observer.counters


@pytest.mark.parametrize("policy,use_faults",
                         [(None, False), ("pid", False), ("pid", True),
                          ("membound", True)])
def test_batch_numpy_b1_counters_match_sequential_exactly(plat, trace_,
                                                          policy,
                                                          use_faults):
    seq_cp, bat_cp = _planes(plat, trace_, policy, use_faults)
    one = bat_cp.design(0)
    for group in ("tile", "link", "island"):
        mine, theirs = getattr(seq_cp, group), getattr(one, group)
        for k in mine:
            assert np.array_equal(mine[k], theirs[k]), (group, k)
    assert float(one.ticks) == float(seq_cp.ticks) == float(T)


@pytest.mark.parametrize("policy,use_faults", [("pid", True), (None, False)])
def test_jax_counters_match_numpy_within_f32_tolerance(plat, trace_, policy,
                                                       use_faults):
    """The scan emits float32 snapshots; every counter must land within
    f32 rounding of the float64 reference — including the integer-valued
    stall/offered channels, which must match exactly."""
    cfg = SimConfig(control_interval=25)
    fkw = fault_kwargs(plat, use_faults)
    seq = SimEngine(plat, config=cfg, observe="counters",
                    **seq_kwargs(plat, policy), **fkw)
    seq.run(trace_)
    sp = seq.observer.counters
    bplat = BatchSimPlatform.stack([plat])
    jx = BatchSimEngine(bplat, config=cfg, backend="jax",
                        observe="counters", **bat_kwargs(bplat, policy),
                        **fkw)
    jx.run(trace_)
    jp = jx.observer.counters.design(0)
    for group in ("tile", "link", "island"):
        mine, theirs = getattr(sp, group), getattr(jp, group)
        for k in mine:
            v, jv = np.asarray(mine[k]), np.asarray(theirs[k])
            tol = 2e-4 * np.maximum(np.abs(v), 1.0) + 1e-6
            assert (np.abs(jv - v) <= tol).all(), (group, k, v, jv)
    assert np.array_equal(sp.tile["stall_ticks"], jp.tile["stall_ticks"])


def test_counters_tie_back_to_engine_histories(plat, trace_):
    """offered/invocations are exactly the admitted/served column sums the
    engine itself kept; energy sums (within fp reassociation) to the
    result's energy integral."""
    eng = SimEngine(plat, observe="counters")
    res = eng.run(trace_)
    cp = eng.observer.counters
    admitted, served = eng.last_histories
    assert np.array_equal(cp.tile["offered"], admitted.sum(axis=0))
    assert np.array_equal(cp.tile["invocations"], served.sum(axis=0))
    assert cp.island["energy_j"].sum() == pytest.approx(res.energy_j,
                                                        rel=1e-9)
    s = cp.summary()
    assert s["ticks"] == T
    assert s["invocations"] == pytest.approx(served.sum())
    assert 0.0 < s["busy_frac"] <= 1.0
    assert s["peak_link_util"] > 0.0


# ---------------------------------------------------------- control trace


def test_trace_rejects_unknown_kind_and_backward_tick():
    tr = ControlTrace()
    tr.emit(5, "run_start", ticks=10)
    with pytest.raises(ValueError, match="unknown trace kind"):
        tr.emit(6, "made_up_kind")
    with pytest.raises(ValueError, match="non-monotonic"):
        tr.emit(4, "run_end")
    # equal ticks are fine (several events can share a tick)
    tr.emit(5, "dfs_commit", version=1)
    assert [e.kind for e in tr.events()] == ["run_start", "dfs_commit"]


def test_trace_ring_bound_and_jsonl_roundtrip():
    tr = ControlTrace(capacity=8)
    for t in range(20):
        tr.emit(t, "dfs_commit", version=t,
                rates=np.asarray([0.5, 1.0]))       # np payloads allowed
    assert len(tr) == 8 and tr.total_emitted == 20
    assert tr.events()[0].tick == 12                # oldest fell off
    back = ControlTrace.from_jsonl(tr.to_jsonl())
    assert [e.to_dict() for e in back.events()] == \
        [e.to_dict() for e in tr.events()]
    assert back.events()[-1].data["rates"] == [0.5, 1.0]


def test_trace_spans_and_counts():
    tr = ControlTrace()
    tr.emit(3, "slo_drop_start", tiles=["a"])
    tr.emit(9, "slo_drop_end", ticks=6)
    tr.emit(12, "slo_drop_start", tiles=["a"])
    tr.emit(15, "slo_drop_end", ticks=3)
    assert tr.spans("slo_drop_start", "slo_drop_end") == [(3, 9), (12, 15)]
    assert tr.counts() == {"slo_drop_start": 2, "slo_drop_end": 2}


def test_full_level_traces_control_and_fault_events(plat, trace_):
    """A PID + fault run at level=full must leave a machine-readable
    story: run_start/run_end bracket, DFS commits, the kill/revive pair —
    with monotonic ticks and registered kinds throughout."""
    eng = SimEngine(plat, config=SimConfig(control_interval=25),
                    observe="full", **seq_kwargs(plat, "pid"),
                    **fault_kwargs(plat, True))
    eng.run(trace_)
    tr = eng.observer.trace
    kinds = tr.counts()
    assert kinds.get("run_start") == 1 and kinds.get("run_end") == 1
    assert kinds.get("dfs_commit", 0) > 0
    assert kinds.get("fault_kill") == 1 and kinds.get("fault_revive") == 1
    ticks = [e.tick for e in tr.events()]
    assert ticks == sorted(ticks)
    assert all(e.kind in TRACE_KINDS for e in tr.events())
    kill = tr.events("fault_kill")[0]
    assert plat.names[2] in kill.subject
    # the whole trace survives a JSONL round trip
    assert len(ControlTrace.from_jsonl(tr.to_jsonl())) == len(tr)


def test_counters_level_skips_tracing(plat, trace_):
    eng = SimEngine(plat, config=SimConfig(control_interval=25),
                    observe="counters", **seq_kwargs(plat, "pid"))
    eng.run(trace_)
    assert len(eng.observer.trace) == 0
    assert eng.observer.counters is not None


# -------------------------------------------------------- observer facade


def test_observer_coercion_and_level_knob():
    assert Observer.coerce(None) is None
    assert Observer.coerce("off") is None
    ob = Observer.coerce("counters")
    assert ob.enabled and not ob.tracing
    assert Observer.coerce("full").tracing
    assert Observer.coerce(ob) is ob
    with pytest.raises(ValueError, match="level"):
        Observer(level="verbose")
    with pytest.raises(TypeError):
        Observer.coerce(3)
    assert LEVELS == ("off", "counters", "full")


def test_observer_reuse_across_runs_resets_trace(plat, trace_):
    """One observer driven through two runs: begin_run() must reset the
    monotonic-tick guard and each run's counters must replace the last
    (second run == fresh-observer second run, not an accumulation)."""
    ob = Observer("full")
    eng = SimEngine(plat, observe=ob)
    eng.run(trace_)
    first = ob.counters.snapshot()
    eng.run(trace_)                      # would raise if the guard leaked
    again = ob.counters
    assert ob.trace.counts().get("run_start") == 1
    assert float(again.ticks) == T
    fresh = SimEngine(plat, observe="counters")
    fresh.run(trace_)
    assert again.allclose(fresh.observer.counters)
    assert np.array_equal(first["tile"]["invocations"],
                          again.tile["invocations"])


def test_lazy_counters_materialize_on_first_read(plat, trace_):
    ob = Observer("counters")
    eng = SimEngine(plat, observe=ob)
    eng.run(trace_)
    assert ob._counters is None and ob._counters_thunk is not None
    cp = ob.counters
    assert isinstance(cp, CounterPlane)
    assert ob._counters_thunk is None   # the thunk ran once and is gone
    assert ob.counters is cp            # second read: cached, not re-built


# -------------------------------------------------------------- profiling


def test_profiler_phases_accumulate():
    prof = Profiler()
    with profiled("phase_a", prof):
        pass
    with profiled("phase_a", prof):
        pass
    with profiled("phase_b", prof):
        pass
    s = prof.summary()
    assert s["phase_a"]["count"] == 2
    assert s["phase_b"]["count"] == 1
    assert s["phase_a"]["total_s"] >= 0.0
    prof.reset()
    assert prof.summary() == {}


def test_summary_books_a_chunk_phase_as_before():
    """``summary()`` of a ``sweep_chunk``-style phase keeps its keys and
    its total and count: the sweep cell reads them after a reset."""
    prof = Profiler()
    for _ in range(3):
        with profiled("sweep_chunk", prof):
            time.sleep(0.002)
    s = prof.summary()
    assert list(s) == ["sweep_chunk"]
    assert set(s["sweep_chunk"]) == {"total_s", "count", "mean_s"}
    assert s["sweep_chunk"]["count"] == 3
    ring = [sp.seconds for sp in prof.spans()]
    assert s["sweep_chunk"]["total_s"] == pytest.approx(sum(ring))
    assert s["sweep_chunk"]["mean_s"] == pytest.approx(sum(ring) / 3)


def test_spans_nest_with_their_parents():
    prof = Profiler()
    with profiled("root", prof):
        with profiled("child", prof):
            with profiled("leaf", prof):
                pass
        with profiled("sibling", prof):
            pass
    with profiled("next_root", prof):
        pass
    by = {s.name: s for s in prof.spans()}
    assert [s.name for s in prof.spans()] == [
        "leaf", "child", "sibling", "root", "next_root"]   # close order
    assert by["root"].parent is None and by["next_root"].parent is None
    assert by["child"].parent == by["root"].seq
    assert by["sibling"].parent == by["root"].seq
    assert by["leaf"].parent == by["child"].seq
    for name in ("child", "sibling", "leaf"):
        s, p = by[name], next(x for x in prof.spans()
                              if x.seq == by[name].parent)
        assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns


def test_a_closed_span_ends_early_and_once():
    prof = Profiler()
    with profiled("outer", prof):
        with profiled("first", prof) as first:
            first.close()
            with profiled("second", prof):
                pass
    by = {s.name: s for s in prof.spans()}
    assert by["second"].parent == by["outer"].seq     # not under "first"
    assert by["first"].end_ns <= by["second"].start_ns
    assert prof.summary()["first"]["count"] == 1
    assert first.seconds == by["first"].seconds


def test_a_decorated_function_records_a_span_per_call():
    prof = Profiler()

    @profiled("step", prof)
    def step(depth):
        return step(depth - 1) if depth else 0

    step(2)
    spans = prof.spans()
    assert [s.name for s in spans] == ["step"] * 3
    assert spans[0].parent == spans[1].seq and spans[1].parent == spans[2].seq


def test_counter_events_sit_under_the_current_span():
    prof = Profiler()
    prof.count("builds")
    with profiled("ranking", prof):
        prof.count("builds", 2)
    ev = [s for s in prof.spans() if s.name == "builds"]
    ranking = next(s for s in prof.spans() if s.name == "ranking")
    assert [e.count for e in ev] == [1, 2]
    assert ev[0].parent is None and ev[1].parent == ranking.seq
    assert ev[1].start_ns == ev[1].end_ns
    assert ranking.start_ns <= ev[1].start_ns <= ranking.end_ns
    assert ranking.count is None
    assert prof.counts == {"builds": 3}


def test_ring_is_bounded_and_counts_what_it_dropped():
    prof = Profiler()
    extra = 10
    for i in range(RING_CAPACITY + extra):
        prof.count("tick", i)
    ring = prof.spans()
    assert len(ring) == RING_CAPACITY
    assert prof.dropped == extra
    assert ring[0].count == extra and ring[-1].count == RING_CAPACITY + extra - 1


def test_reset_clears_totals_but_not_the_ring():
    prof = Profiler()
    with profiled("phase", prof):
        prof.count("c")
    prof.reset()
    assert prof.summary() == {} and prof.counts == {}
    assert [s.name for s in prof.spans()] == ["c", "phase"]


def test_a_full_collection_is_recorded_as_gc_full():
    prof = get_profiler()
    with profiled("holder"):
        gc.collect()
        gc.collect(0)                   # a young generation: not recorded
    spans = prof.spans()
    holder = next(s for s in reversed(spans) if s.name == "holder")
    gcs = [s for s in spans if s.name == "gc_full"
           and holder.start_ns <= s.start_ns and s.end_ns <= holder.end_ns]
    assert len(gcs) == 1
    assert gcs[0].parent == holder.seq


def test_spans_land_in_the_host_plane_on_the_ring_clock(tmp_path):
    """Each span is a ``TraceAnnotation`` of its name in a traced run's
    ``/host:CPU`` plane, starting within 1 ms of the ring's stamp."""
    import jax
    import jax.numpy as jnp
    prof = Profiler()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiled("pb_outer", prof):
            with profiled("pb_inner", prof):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path[0])
    env = dict(data.find_plane_with_name("Task Environment").stats)
    t0 = env["profile_start_time"]
    host = data.find_plane_with_name("/host:CPU")
    seen = {ev.name: t0 + ev.start_ns for line in host.lines
            for ev in line.events if ev.name.startswith("pb_")}
    for s in prof.spans():
        assert s.name in seen, (s.name, sorted(seen))
        assert abs(seen[s.name] - s.start_ns) < 1e6


def test_jitted_names_the_device_trace_readers_look_up(plat):
    """The sweep evaluator lowers as module ``jit_fn`` and the cached
    scan as ``jit_run_scan``: ``sweep_eval_roofline`` and
    ``tick_scan_roofline`` find them in the device trace by those
    names."""
    from repro.core.dse import _flat_point_evaluator
    ev = _flat_point_evaluator(1, 1, 0, ((1.0, 0.5),), 1.0, 1.0, 1.0, 0.1,
                               2.0, 1.0, 1.0)
    # one accelerator: K, f_noc, f_acc, f_tg and position axes, 2 values
    # each, and their five value tables
    axes = np.full(5, 2, dtype=np.int32)
    tables = np.ones((5, 32), dtype=np.float32)
    assert ev.lower(4, np.zeros_like(axes), axes,
                    tables).as_text().startswith("module @jit_fn")

    bplat = BatchSimPlatform.stack([plat, plat])
    eng = BatchSimEngine(bplat, backend="jax")
    seen = []
    orig = eng._cached_scan

    def spy(sig, build):
        fn = orig(sig, build)

        def call(*args):
            seen.append(fn.lower(*args).as_text())
            return fn(*args)
        return call
    eng._cached_scan = spy
    eng.run(poisson_trace(4000.0, 20, plat.n_tiles, dt=DT, seed=1))
    assert seen and seen[0].startswith("module @jit_run_scan")


# -------------------------------------------------------- counter scoping


def test_counterplane_reset_scopes_like_manual_reset():
    cp = CounterPlane(3, 2, 2, tile_names=("a", "b", "c"))
    for k in cp.tile:
        cp.tile[k][:] = 7.0
    cp.link["flits"][:] = 5.0
    cp.island["energy_j"][:] = 2.0
    cp.ticks = np.asarray(9.0)
    cp.reset(kinds=["busy_ticks"], tiles=["b", 2])
    assert list(cp.tile["busy_ticks"]) == [7.0, 0.0, 0.0]
    assert (cp.tile["invocations"] == 7.0).all()    # untouched kind
    cp.reset(kinds=["flits"])
    assert (cp.link["flits"] == 0.0).all()
    assert (cp.island["energy_j"] == 2.0).all()
    with pytest.raises(ValueError, match="unknown counter kinds"):
        cp.reset(kinds=["made_up"])
    cp.reset()
    assert float(cp.ticks) == 0.0
    assert all((v == 0.0).all() for v in cp.tile.values())


# --------------------------------------------------------- metrics export


def test_metrics_registry_semantics_and_prometheus_roundtrip():
    reg = MetricsRegistry()
    reg.counter("x_total", "adds", labels={"t": "a"}, value=2.0)
    reg.counter("x_total", labels={"t": "a"}, value=3.0)
    reg.gauge("g", "sets", value=1.5)
    reg.gauge("g", value=2.5)
    reg.histogram("h_seconds", "obs", value=0.003)
    reg.histogram("h_seconds", "obs", value=4.2)
    assert reg.get("x_total", {"t": "a"}) == 5.0    # counter accumulates
    assert reg.get("g") == 2.5                      # gauge overwrites
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    parsed = parse_prometheus_text(reg.render_prometheus())
    assert set(parsed) == {"x_total", "g", "h_seconds"}
    assert parsed["x_total"]["type"] == "counter"
    assert parsed["x_total"]["samples"] == [({"t": "a"}, 5.0)]
    assert parsed["g"]["samples"] == [({}, 2.5)]
    hist = parsed["h_seconds"]
    assert hist["type"] == "histogram"
    counts = [v for lb, v in hist["samples"]
              if lb.get("__sample__") == "count"]
    sums = [v for lb, v in hist["samples"] if lb.get("__sample__") == "sum"]
    assert counts == [2] and sums == [pytest.approx(4.203)]


def test_export_metrics_roundtrips_engine_counters(plat, trace_):
    eng = SimEngine(plat, config=SimConfig(control_interval=25),
                    observe="full", **seq_kwargs(plat, "pid"))
    res = eng.run(trace_)
    ob = eng.observer
    reg = export_metrics(counters=ob.counters, trace=ob.trace,
                         telemetry=res.telemetry)
    text = reg.render_prometheus()
    parsed = parse_prometheus_text(text)
    assert set(parsed) == set(reg.names()) and parsed
    # a per-tile counter round-trips to the exact engine-side value
    name = plat.names[0]
    served0 = float(eng.last_histories[1].sum(axis=0)[0])
    assert reg.get("sim_tile_invocations_total",
                   {"tile": name}) == pytest.approx(served0)
    got = [v for lb, v in parsed["sim_tile_invocations_total"]["samples"]
           if lb == {"tile": name}]
    assert got == [pytest.approx(served0)]
    # trace kinds surface as labeled event counters
    kinds = {lb["kind"] for lb, _ in
             parsed["sim_trace_events_total"]["samples"]}
    assert {"run_start", "run_end"} <= kinds
    # telemetry gauges carry the latest row
    assert reg.get("sim_telemetry_tick") is not None


# ------------------------------------------------- closed_loop_score hook


def test_closed_loop_score_observe_attaches_counters(plat):
    from repro.core.dse import closed_loop_score, grid_sweep
    from repro.sim import diurnal_trace
    m = SoCPerfModel()
    wls = [AccelWorkload("dfmul", 8.70, 1.1),
           AccelWorkload("fft2d", 145.0, 20.8)]
    res = grid_sweep(m, wls, ks=(1, 2), acc_rates=(0.5, 1.0),
                     noc_rates=(1.0,), tg_rates=(1.0,),
                     positions=((1, 1), (3, 3)), n_tg=2)
    trace = lambda seed: diurnal_trace(3000.0, 250, 2,     # noqa: E731
                                       dt=1e-3, seed=seed)
    base = closed_loop_score(res, trace, model=m, top=2)
    assert base.counters is None
    for kwargs in (dict(), dict(batch=False)):
        sc = closed_loop_score(res, trace, model=m, top=2,
                               observe="counters", **kwargs)
        assert sc.counters is not None and len(sc.counters) == 2
        for s in sc.counters:
            assert s["ticks"] == 250
            assert s["invocations"] > 0 and s["energy_j"] > 0
        # monitoring must not move the ranking
        assert np.array_equal(sc.ranked_indices(), base.ranked_indices())
        assert np.allclose(sc.p99_latency_s, base.p99_latency_s)
