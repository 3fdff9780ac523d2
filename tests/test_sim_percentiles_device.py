"""The co-sim's latency percentiles on the device
(:mod:`repro.sim.percentiles`).

The device program plus the host's fallback must give p50/p99 *bitwise*
equal to the float64 reference :func:`repro.sim.engine.latency_percentiles`
on the same float32 histories: on seeded fluid-queue and random
histories, on engineered near-ties (which must come out uncertified, be
counted under ``percentile_host_fallbacks`` and still be equal), through
the ``jax`` and ``pallas`` backends, once per process for a shape, and
sharded over devices.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sim import percentiles as P
from repro.sim.engine import latency_percentiles
from repro.sim.observe import get_profiler

DT = 1e-3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def host(adm, srv, drp=None):
    """The reference: the host loop, design by design, in float64 (as the
    engines hand it their float32 histories)."""
    adm, srv, drp = [None if h is None else np.asarray(h, np.float64)
                     for h in (adm, srv, drp)]
    out = np.array([latency_percentiles(
        adm[:, b], srv[:, b], DT,
        queue_drops=None if drp is None else drp[:, b])
        for b in range(adm.shape[1])])
    return out[:, 0], out[:, 1]


def device(adm, srv, drp=None):
    """(p50, p99, per-design status, fallbacks counted) of the device
    path on float32 histories."""
    f64 = [None if h is None else np.asarray(h, np.float64)
           for h in (adm, srv, drp)]
    before = get_profiler().counts.get(P.FALLBACK_COUNTER, 0)
    bins = P.certified_bins(jnp.asarray(adm), jnp.asarray(srv),
                            None if drp is None else jnp.asarray(drp))
    p50, p99 = P.percentiles_from_bins(bins, f64[0], f64[1], DT, f64[2])
    counted = get_profiler().counts[P.FALLBACK_COUNTER] - before
    return p50, p99, np.asarray(bins)[:, 2], counted


def assert_bitwise(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.float64
        assert np.array_equal(g, w, equal_nan=True), (g, w)


def fluid(seed, T=240, B=24, A=3, *, integer=False, zero_share=0.0,
          load=0.8, drops=False):
    """Histories of FIFO fluid queues: arrivals (gamma, or Poisson counts
    with ``integer``), a per-tile capacity, and with ``drops`` a share of
    the backlog dropped each tick."""
    rng = np.random.default_rng(seed)
    rate = rng.uniform(0.5, 3.0, (B, A))
    arr = (rng.poisson(rate, (T, B, A)) if integer
           else rng.gamma(2.0, rate / 2.0, (T, B, A))).astype(np.float32)
    arr[rng.random((T, B, A)) < zero_share] = 0.0
    cap = (rate / load).astype(np.float32)
    q = np.zeros((B, A), np.float32)
    hist = np.zeros((3, T, B, A), np.float32)
    for t in range(T):
        q = q + arr[t]
        s = np.minimum(q, cap)
        q = q - s
        d = (np.where(q > 4.0, np.float32(0.25) * q, np.float32(0.0))
             if drops else np.zeros_like(q))
        q = q - d
        hist[:, t] = arr[t], s, d
    return hist[0], hist[1], (hist[2] if drops else None)


CASES = {
    "fractional": dict(),
    "integer": dict(integer=True),
    "zero_ticks": dict(zero_share=0.6),
    "unfinished_tails": dict(load=2.5),
    "queue_drops": dict(drops=True, load=1.2),
    "integer_drops": dict(integer=True, drops=True, load=1.2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_equals_host_bitwise(case):
    adm, srv, drp = fluid(17, **CASES[case])
    p50, p99, status, counted = device(adm, srv, drp)
    assert_bitwise((p50, p99), host(adm, srv, drp))
    assert counted == int((status == P.UNCERTIFIED).sum())
    # the device answers for most designs itself
    assert (status == P.CERTIFIED).sum() >= 0.75 * len(status)


def test_unfinished_tails_leave_samples_undone():
    """Overloaded queues keep requests past the last tick
    (``depart >= T``): those are not samples, on either side."""
    adm, srv, _ = fluid(3, load=2.5)
    T = adm.shape[0]
    ca, cs = np.cumsum(adm[:, 0, 0], dtype=np.float64), np.cumsum(
        srv[:, 0, 0], dtype=np.float64)
    depart = np.searchsorted(cs, ca - 0.5 * adm[:, 0, 0], side="left")
    assert (depart >= T).sum() > T // 4
    assert_bitwise(device(adm, srv)[:2], host(adm, srv))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_histories(seed):
    """Histories no queue could produce (exits ahead of arrivals, so
    negative bins) still reduce to the same arithmetic."""
    rng = np.random.default_rng(seed)
    adm = rng.gamma(1.0, 1.0, (150, 16, 2)).astype(np.float32)
    srv = rng.gamma(1.0, 1.0, (150, 16, 2)).astype(np.float32)
    assert_bitwise(device(adm, srv)[:2], host(adm, srv))


def test_a_design_with_nothing_done_is_nan():
    adm, srv, _ = fluid(5, B=4)
    adm[:, 1] = 0.0                     # nothing admitted
    srv[:, 2] = 0.0                     # nothing ever leaves
    p50, p99, status, _ = device(adm, srv)
    assert_bitwise((p50, p99), host(adm, srv))
    assert np.isnan(p50[1:3]).all() and np.isnan(p99[1:3]).all()
    assert (status[1:3] == P.NO_SAMPLES).all()


def test_one_tick():
    adm, srv, _ = fluid(6, T=1, B=8)
    assert_bitwise(device(adm, srv)[:2], host(adm, srv))


def test_float32_sums_cannot_settle_what_the_device_does():
    """Curves near 2^21, each departure decided by a gap of a few 2^-13
    that float32 sums lose there: the compensated sums settle every one,
    and certify the designs."""
    rng = np.random.default_rng(8)
    T, B = 2048, 4
    adm = np.full((T, B, 1), 1024.0, np.float32)
    # exits cs[t] = mid[t] + eps[t]: tick t's batch departs at t for
    # eps[t] >= 0 and at t + 1 otherwise
    eps = (rng.choice([-3, -2, -1, 1, 2, 3], (T, B, 1), p=[.1] * 3 + [
        7 / 30] * 3) * 2.0 ** -13)
    srv = np.diff(512.0 + 1024.0 * np.arange(T)[:, None, None] + eps,
                  axis=0, prepend=0.0).astype(np.float32)
    ca = np.cumsum(adm[:, 0, 0])
    f32 = np.searchsorted(np.cumsum(srv[:, 0, 0]), ca - 512.0)
    f64 = np.searchsorted(np.cumsum(srv[:, 0, 0], dtype=np.float64),
                          ca.astype(np.float64) - 512.0)
    assert (f64 - np.arange(T) == (eps[:, 0, 0] < 0)).all()
    assert not np.array_equal(f32, f64)
    p50, p99, status, counted = device(adm, srv)
    assert_bitwise((p50, p99), host(adm, srv))
    assert counted == 0 and (status == P.CERTIFIED).all()


def near_ties():
    """Designs the device must not certify, each beside a sound one.

    0: a mid-rank exactly on a departure (an exact tie);
    1, 2: a mid-rank one float64 ulp and half an ulp above a departure,
       which the host's own float64 rounding puts on it: the host
       answers bin -1, exact arithmetic bin 0, and only the fallback
       gives the host's answer;
    3: integer weights whose p50 target lies on a bin edge;
    4: integer weights whose p99 target lies on a bin edge;
    5: the same histories as 4, one request more: certified."""
    T, B = 4, 6
    adm = np.zeros((T, B, 1), np.float32)
    srv = np.zeros((T, B, 1), np.float32)
    adm[:2, 0, 0], srv[:2, 0, 0] = (1.0, 1.0), (0.5, 1.5)
    adm[:2, 1, 0], srv[:2, 1, 0] = (2.0 ** -52, 2.0), (1.0, 5.0)
    adm[:2, 2, 0], srv[:2, 2, 0] = (2.0 ** -53, 2.0), (1.0, 5.0)
    adm[:2, 3, 0], srv[:3, 3, 0] = (1.0, 1.0), (1.0, 0.0, 1.0)
    adm[:2, 4, 0], srv[:3, 4, 0] = (99.0, 1.0), (99.0, 0.0, 1.0)
    adm[:2, 5, 0], srv[:3, 5, 0] = (99.0, 2.0), (99.0, 0.0, 2.0)
    return adm, srv


def test_near_ties_are_not_certified_but_counted_and_equal():
    adm, srv = near_ties()
    p50, p99, status, counted = device(adm, srv)
    assert_bitwise((p50, p99), host(adm, srv))
    assert list(status) == [P.UNCERTIFIED] * 5 + [P.CERTIFIED]
    assert counted == 5
    # designs 1 and 2: the host's answer is not the exact one
    assert p50[1] == p50[2] == -0.5 * DT


def test_a_target_inside_the_hosts_rounding_is_not_certified():
    """Weights 2^30 in bin 0, 2^30 and 3 * 2^-30 in bin 1 (on a second
    tile, whose departures are certain): the host's float64 sums lose
    the 3 * 2^-30, put the p50 target on the edge of bin 0 and answer bin
    0, where exact sums give bin 1.  The device must leave it to the
    host; a sound design beside it is certified."""
    adm = np.zeros((3, 2, 2), np.float32)
    srv = np.zeros((3, 2, 2), np.float32)
    for b, tiny in enumerate((3.0, 0.0)):
        adm[:, b, 0], srv[:, b, 0] = (2.0 ** 30, 2.0 ** 30, 0.0), (
            2.0 ** 30, 0.0, 2.0 ** 30)
        adm[0, b, 1], srv[1, b, 1] = tiny * 2.0 ** -30, tiny * 2.0 ** -30
    srv[2, 1, 0] += 256.0               # design 1: clear of every edge
    adm[1, 1, 0] += 256.0
    p50, p99, status, counted = device(adm, srv)
    assert_bitwise((p50, p99), host(adm, srv))
    assert p50[0] == 0.5 * DT
    assert list(status) == [P.UNCERTIFIED, P.CERTIFIED] and counted == 1


def test_entries_the_device_cannot_read_are_left_to_the_host():
    """Negative, subnormal and non-finite entries: uncertified, never
    guessed (the TPU flushes subnormals to zero)."""
    adm, srv, _ = fluid(9, B=4)
    adm[5, 0, 0] = -1.0
    adm[7, 1, 1] = 1e-40
    srv[9, 2, 2] = np.inf
    p50, p99, status, counted = device(adm, srv)
    assert_bitwise((p50, p99), host(adm, srv))
    assert list(status[:3]) == [P.UNCERTIFIED] * 3
    assert status[3] == P.CERTIFIED and counted == 3


def test_host_arrays_and_other_dtypes_take_the_host_loop():
    adm, srv, _ = fluid(4, B=2)
    assert P.certified_bins(adm, srv) is None
    assert P.certified_bins(jnp.asarray(adm, jnp.bfloat16),
                            jnp.asarray(srv, jnp.bfloat16)) is None
    assert P.certified_bins(jnp.zeros((0, 2, 3)),
                            jnp.zeros((0, 2, 3))) is None


# -- through the engines --------------------------------------------------

def _engine_case(backend, faults):
    from repro.core.dfs import BatchPIDRatePolicy
    from repro.core.perfmodel import AccelWorkload, SoCPerfModel
    from repro.sim import (BatchControllerHarness, BatchSimEngine,
                           BatchSimPlatform, FaultSchedule, SimConfig,
                           SimEngine, SimPlatform, SLOConfig,
                           diurnal_trace)
    pos = [(0, 1), (0, 2), (1, 1), (1, 2)]
    plats = [SimPlatform.build(
        SoCPerfModel(), [AccelWorkload("dfmul", 8.70, 1.1, replication=k)
                         for _ in pos], pos, n_tg=2, req_mb=0.005)
        for k in (2, 4, 8)]
    bplat = BatchSimPlatform.stack(plats)
    cap = SimEngine(plats[0]).capacity_rps()
    tr = diurnal_trace(cap * 0.9, 300, len(pos), dt=DT, depth=0.5, seed=2)
    kw = {}
    if faults:
        kw = dict(faults=FaultSchedule().kill_tile(plats[0].names[1],
                                                   start=80, end=200),
                  slo=SLOConfig(deadline_s=0.02))
    ctl = BatchControllerHarness(bplat.islands, bplat.rates,
                                 BatchPIDRatePolicy(target=0.7),
                                 tile_names=bplat.names,
                                 queue_guard_ticks=3.0)
    eng = BatchSimEngine(bplat, config=SimConfig(control_interval=25),
                         controller=ctl, backend=backend, **kw)
    return eng, tr


@pytest.mark.parametrize("backend,faults", [("jax", False), ("jax", True),
                                            ("pallas", False)])
def test_backends_match_the_host_loop_on_their_histories(backend, faults):
    """``BatchSimResult.p50/p99`` of the device backends equal what the
    numpy backend's host loop makes of the same float32 histories."""
    eng, tr = _engine_case(backend, faults)
    prof = get_profiler()
    seen = sum(s.name == P.FALLBACK_COUNTER for s in prof.spans())
    res = eng.run(tr)
    adm, srv = eng.last_histories
    drp = (eng.last_fault_histories or {}).get("queue_drops")
    assert (drp is not None) == faults
    if faults:
        assert drp.sum() > 0
    assert_bitwise((res.p50_latency_s, res.p99_latency_s),
                   host(adm, srv, drp))
    # the device path ran, and counted its fallbacks (none or more)
    assert sum(s.name == P.FALLBACK_COUNTER for s in prof.spans()) > seen
    assert np.isfinite(res.p99_latency_s).all()


def test_closed_loop_score_compiles_the_percentiles_once():
    """Two rankings of the same shape build the device program at most
    once, and the second builds nothing (the JAX monitoring events that
    ``cosim_compile_s_per_ranking`` reads); the percentiles add no tick
    loop build."""
    from repro.core.dse import closed_loop_score, grid_sweep
    from repro.core.perfmodel import AccelWorkload, SoCPerfModel
    from repro.sim import diurnal_trace
    m = SoCPerfModel()
    res = grid_sweep(m, [AccelWorkload("dfadd", 9.22, 0.9),
                         AccelWorkload("dfmul", 8.70, 1.1)],
                     ks=(1, 2, 4), acc_rates=(0.2, 0.6, 1.0),
                     noc_rates=(0.5, 1.0), n_tg=2)
    idx = res.topk_indices(13)
    built = []

    def listen(event, duration, **kw):
        if (event == "/jax/core/compile/backend_compile_duration"
                and kw.get("fun_name") == "jit(_bins)"):
            built.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    prof = get_profiler()
    try:
        counts = []
        for seed in (1, 2):
            tr = diurnal_trace(1500.0, 211, 2, dt=DT, depth=0.4, seed=seed)
            loops = prof.counts.get("tick_loop_builds", 0)
            n = len(built)
            s = closed_loop_score(res, tr, model=m, indices=idx,
                                  req_mb=0.002, backend="jax")
            counts.append(len(built) - n)
            assert prof.counts.get("tick_loop_builds", 0) - loops == 1
            assert np.isfinite(s.p99_latency_s).all()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert counts[0] <= 1 and counts[1] == 0, counts


def test_sharded_histories_give_the_same_percentiles():
    """``devices=2`` on virtual CPU devices: the program runs on each
    shard's designs, with no collective, and gives the p50/p99 of
    ``devices=None``."""
    code = """
        import numpy as np
        import jax
        assert len(jax.devices()) == 2, jax.devices()
        from repro.sim import BatchSimEngine, BatchSimPlatform, SimEngine
        from repro.sim import percentiles as P
        from test_sim_batch import make_platform, make_trace
        cap = SimEngine(make_platform(4, k=2)).capacity_rps()
        tr = make_trace("diurnal", cap, ticks=260, n=4)
        plats = [make_platform(4, k=k) for k in (2, 2, 4, 8, 8)]
        out = {}
        for devices in (None, 2):
            eng = BatchSimEngine(BatchSimPlatform.stack(plats),
                                 backend="jax", devices=devices)
            r = eng.run(tr)
            out[devices] = (r.p50_latency_s, r.p99_latency_s)
        for a, b in zip(out[None], out[2]):
            assert np.array_equal(a, b, equal_nan=True), (a, b)
        # the sharded program keeps every design on its own device
        adm, srv = eng.last_histories
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = jax.make_mesh((2,), ("d",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        sh = NamedSharding(mesh, PartitionSpec(None, "d", None))
        a = jax.device_put(adm[:, :4].astype(np.float32), sh)
        s = jax.device_put(srv[:, :4].astype(np.float32), sh)
        text = P._bins_jit.lower(a, s, None).compile().as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            assert op not in text, op
        assert P.certified_bins(a, s).sharding.spec[0] == "d"
        print("ok")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(ROOT, "src"),
                                         os.path.dirname(__file__)])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")
