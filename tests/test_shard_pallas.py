"""Raw-speed PR: multi-device sharding + the Pallas fused-tick backend.

Four guarantee families:

* **Pallas differential parity** — the fused tick kernel
  (``repro.kernels.tick_sim``, interpret mode on CPU) matches the NumPy
  float64 reference engine within float32 tolerance and the
  ``jax.lax.scan`` backend bit-tightly, open-loop and with every
  controller the shared control lowering supports (membound / PID /
  custom ``jax_step`` policies) — swap counts exactly.  Fresh policy and
  platform instances per backend run: stateful policies (PID integral,
  EWMA) otherwise leak state across backends and fake a divergence.
* **Shard-count invariance** — 1 vs N virtual devices
  (``--xla_force_host_platform_device_count``, subprocess arms like
  ``test_distributed.py``) produce *identical* sweep Pareto fronts and
  bitwise-identical co-sim scores: ``shard_map`` only partitions
  per-design/per-point math.
* **jit-cache keying** — the batched engine's scan cache is keyed on an
  explicit signature (trace length, cadence, dt, fault class,
  policy/balancer digests, model scalars), so a changed dt or a retuned
  policy misses the cache instead of replaying a stale executable, and
  the cache is LRU-bounded at ``_SCAN_CACHE_MAX``.
* **bounded module caches** — the route/table caches in ``core.noc``,
  the jitted kernel cache in ``core.perfmodel``, the sharded evaluator
  cache in ``core.dse`` and the mesh cache in ``repro.shard`` all stay
  within their declared bounds under a 1k-distinct-config sweep.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro import shard
from repro.core.dfs import (BatchEWMAUtilizationPolicy,
                            BatchMemoryBoundPolicy, BatchPIDRatePolicy)
from repro.sim import (BatchSimEngine, BatchSimPlatform, FaultSchedule,
                       LoadBalancer, SimConfig, SimEngine, SLOConfig,
                       constant_trace)
from test_sim_batch import batch_controller, make_platform, make_trace

ROOT = os.path.join(os.path.dirname(__file__), "..")

POLICIES = {
    "open": None,
    "membound": lambda: BatchMemoryBoundPolicy(threshold=0.5, low_rate=0.3),
    "pid": lambda: BatchPIDRatePolicy(target=0.7),
    "ewma": lambda: BatchEWMAUtilizationPolicy(alpha=0.4, target=0.65),
}

RTOL, ATOL = 2e-3, 1e-2         # f32 kernel vs f64 reference


def _fresh_engine(backend, policy_key, *, B=3, ci=25):
    """A fresh platform + controller + engine per backend run — rates and
    policy state mutate in place during a run."""
    plats = [make_platform(4, k=k) for k in (2, 4, 8)][:B]
    bplat = BatchSimPlatform.stack(plats)
    pf = POLICIES[policy_key]
    ctl = (None if pf is None
           else batch_controller(bplat, pf(), queue_guard_ticks=3.0))
    return BatchSimEngine(bplat, config=SimConfig(control_interval=ci),
                          controller=ctl, backend=backend)


def _trace(kind="diurnal", ticks=300, seed=3):
    cap = SimEngine(make_platform(4, k=2)).capacity_rps()
    return make_trace(kind, cap, ticks=ticks, n=4, seed=seed)


def _check_close(a, b, label, rtol=RTOL, atol=ATOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol, err_msg=label)


def _assert_parity(r, ref, *, rtol=RTOL, atol=ATOL):
    for f in ("completed", "energy_j", "p99_latency_s", "throughput_rps"):
        _check_close(getattr(r, f), getattr(ref, f), f, rtol, atol)
    _check_close(r.residual, ref.residual, "residual", rtol, max(atol, 1e-2))
    np.testing.assert_array_equal(np.asarray(r.swaps), np.asarray(ref.swaps))


# ------------------------------------------------ pallas: differential
@pytest.mark.parametrize("policy", list(POLICIES))
def test_pallas_matches_numpy_f64_reference(policy):
    tr = _trace()
    ref = _fresh_engine("numpy", policy).run(tr)
    r = _fresh_engine("pallas", policy).run(tr)
    _assert_parity(r, ref)


@pytest.mark.parametrize("policy", list(POLICIES))
def test_pallas_matches_jax_scan_backend(policy):
    """Same float32 math, two executions (scan vs fused kernel): much
    tighter than the f64 comparison."""
    tr = _trace()
    ref = _fresh_engine("jax", policy).run(tr)
    r = _fresh_engine("pallas", policy).run(tr)
    _assert_parity(r, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", ["constant", "poisson", "diurnal", "mmpp"])
def test_pallas_b1_matches_sequential_engine(kind):
    """B=1 through the fused kernel vs the per-design sequential engine
    (the same reference chain the scan backend is validated against)."""
    plat = make_platform(4, k=4)
    cap = SimEngine(plat).capacity_rps()
    tr = make_trace(kind, cap, ticks=400, n=4)
    seq = SimEngine(plat).run(tr)
    bat = BatchSimEngine(BatchSimPlatform.stack([plat]),
                         backend="pallas").run(tr)
    _check_close(bat.completed[0], seq.completed, "completed")
    _check_close(bat.energy_j[0], seq.energy_j, "energy_j")
    _check_close(bat.residual[0], seq.residual, "residual")
    _check_close(bat.p99_latency_s[0], seq.p99_latency_s, "p99",
                 atol=2 * tr.dt)


def run_pallas_case(seed, ticks, kind, policy):
    """One fuzz case: a random short trace through the fused kernel must
    agree with the f64 reference and conserve work."""
    tr = _trace(kind, ticks=ticks, seed=seed % 97)
    ref = _fresh_engine("numpy", policy, B=2).run(tr)
    r = _fresh_engine("pallas", policy, B=2).run(tr)
    _assert_parity(r, ref)
    comp = np.asarray(r.completed)
    resid = np.asarray(r.residual)
    assert np.all(comp >= 0.0) and np.all(resid >= -1e-6)
    admitted = comp + resid
    _check_close(admitted, np.asarray(ref.completed) + np.asarray(ref.residual),
                 "conservation")


def test_pallas_differential_seeded():
    for seed, ticks, kind, policy in [(0, 60, "diurnal", "open"),
                                      (7, 90, "constant", "pid"),
                                      (23, 120, "diurnal", "pid"),
                                      (41, 45, "constant", "open")]:
        run_pallas_case(seed, ticks, kind, policy)


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=0, max_value=10 ** 6),
           st.integers(min_value=40, max_value=120),
           st.sampled_from(["constant", "diurnal"]),
           st.sampled_from(["open", "pid"]))
    def test_pallas_differential_fuzzed(seed, ticks, kind, policy):
        run_pallas_case(seed, ticks, kind, policy)


def test_pallas_unsupported_features_raise():
    """Faults, SLO, balancer and the observer plane are scan-side
    bookkeeping the kernel does not carry — explicit refusal, not a
    silently wrong answer."""
    plat = make_platform(4)
    tr = _trace(ticks=50)
    mk = lambda **kw: BatchSimEngine(BatchSimPlatform.stack([plat]),  # noqa: E731
                                     backend="pallas", **kw)
    with pytest.raises(NotImplementedError, match="fault"):
        mk(faults=FaultSchedule().kill_tile(plat.names[0], start=10)).run(tr)
    with pytest.raises(NotImplementedError, match="SLO"):
        mk(slo=SLOConfig(deadline_s=0.05)).run(tr)
    with pytest.raises(NotImplementedError, match="balancer"):
        mk(balancer=LoadBalancer([(plat.names[0], plat.names[1])],
                                 plat.names)).run(tr)
    with pytest.raises(NotImplementedError, match="observer"):
        mk(observe="counters").run(tr)


# ------------------------------------------------ jit-cache keying
def test_jit_cache_distinct_dt_no_collision():
    """Two traces with the same tick count but different dt must compile
    (and answer) separately — dt is baked into the traced tick math, so
    a (T, ci)-only cache key replayed the first dt's executable."""
    eng = _fresh_engine("jax", "open", B=1)
    cap = SimEngine(make_platform(4, k=2)).capacity_rps()
    tr_a = constant_trace(cap * 0.6, 200, 4, dt=1e-3)
    tr_b = constant_trace(cap * 0.6, 200, 4, dt=2e-3)
    ra = eng.run(tr_a)
    rb = eng.run(tr_b)
    assert len(eng._jax_cache) == 2, "dt missing from the scan cache key"
    # the dt actually took effect: energy integrates power * dt
    ref_b = _fresh_engine("numpy", "open", B=1).run(tr_b)
    _check_close(rb.energy_j, ref_b.energy_j, "energy@dt2")
    assert not np.allclose(ra.energy_j, rb.energy_j, rtol=1e-3)


def test_jit_cache_policy_retune_misses():
    """Retuning a policy in place (same object, new gains) changes the
    compile-time constants the lowering baked in — the digest must miss."""
    eng = _fresh_engine("jax", "pid")
    tr = _trace(ticks=150)
    eng.run(tr)
    assert len(eng._jax_cache) == 1
    eng.controller.policy.kp *= 10.0
    eng.controller.policy.target = 0.5
    eng.run(tr)
    assert len(eng._jax_cache) == 2, "retuned policy hit a stale executable"

    # custom jax_step policies contribute via jax_cache_key()
    eng2 = _fresh_engine("jax", "ewma")
    eng2.run(tr)
    eng2.controller.policy.alpha = 0.9
    eng2.run(tr)
    assert len(eng2._jax_cache) == 2


def test_jit_cache_bounded_eviction():
    """> _SCAN_CACHE_MAX distinct signatures stay bounded (LRU)."""
    from repro.sim import batch as batch_mod
    eng = _fresh_engine("jax", "open", B=1)
    cap = SimEngine(make_platform(4, k=2)).capacity_rps()
    n_sigs = batch_mod._SCAN_CACHE_MAX + 3
    for i in range(n_sigs):
        eng.run(constant_trace(cap * 0.6, 40 + i, 4, dt=1e-3))
    assert len(eng._jax_cache) == batch_mod._SCAN_CACHE_MAX
    # and the newest signature is resident (a hit, not a rebuild)
    before = dict(eng._jax_cache)
    eng.run(constant_trace(cap * 0.6, 40 + n_sigs - 1, 4, dt=1e-3))
    assert dict(eng._jax_cache).keys() == before.keys()


# ------------------------------------------------ bounded module caches
def test_module_caches_bounded_over_1k_configs():
    from repro.core import dse as dse_mod
    from repro.core import noc as noc_mod

    # noc: a 1k-distinct-config stream through the table/route caches
    for i in range(1000):
        cfg = noc_mod.NocConfig(rows=2 + i % 5, cols=2 + (i // 5) % 7,
                                link_bw=1.0 + 0.001 * i)
        noc_mod.routing_tables(cfg)
        noc_mod.hops(cfg, (0, 0), (cfg.rows - 1, cfg.cols - 1))
    for fn in (noc_mod.routing_tables, noc_mod._xy_route_cached,
               noc_mod.hops):
        info = fn.cache_info()
        assert info.maxsize is not None and info.currsize <= info.maxsize, \
            (fn.__name__, info)
    assert noc_mod.routing_tables.cache_info().currsize \
        <= noc_mod._TABLE_CACHE_SIZE

    # dse: the sharded flat-point evaluator cache is scalar-keyed + bounded
    # (the layout is two flags; point counts and tables are call data)
    assert dse_mod._flat_point_evaluator.cache_info().maxsize == 8
    for i in range(20):
        dse_mod._flat_point_evaluator(1, 2, i, ((1.0, 0.1), (2.0, 0.01)),
                                      0.1, 0.07, 1.0, 0.03, 2.0, 8.0, 0.5,
                                      tech=i % 2 == 1,
                                      independent=i % 3 == 1)
    info = dse_mod._flat_point_evaluator.cache_info()
    assert info.currsize <= 8, info

    # shard: mesh cache is (count, axis-name)-keyed and explicitly bounded
    for i in range(100):
        shard.device_mesh(1, f"axis{i}")
    assert shard.mesh_cache_size() <= shard._MESH_CACHE_MAX


# ------------------------------------------------ shard helpers (local)
def test_shard_resolve_and_pad_helpers():
    assert shard.resolve_devices(None) == 1
    assert shard.resolve_devices("auto") == shard.device_count()
    n = shard.device_count()
    assert shard.resolve_devices(n) == n
    with pytest.raises(ValueError, match="sees only"):
        shard.resolve_devices(n + 1)
    with pytest.raises(AssertionError):
        shard.resolve_devices(0)
    assert shard.shard_len(5, 4) == 8 and shard.shard_len(8, 4) == 8
    a = np.arange(12, dtype=np.float64).reshape(3, 4)
    p = shard.pad_axis(a, 4, axis=0)
    assert p.shape == (4, 4)
    np.testing.assert_array_equal(p[:3], a)
    np.testing.assert_array_equal(p[3], a[0])       # row-0 filler
    assert shard.pad_axis(a, 3, axis=0) is a        # already even


# ------------------------------------------------ shard-count invariance
def _run(code: str, devices: int = 4) -> str:
    """Subprocess arm with N virtual CPU devices (device count is fixed
    at the first jax import, so in-process tests can't flip it)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), os.path.dirname(__file__)])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_shard_sweep_invariance_1_vs_4_devices():
    """grid_sweep(devices=4) == grid_sweep(devices=1): identical Pareto
    front, top-k survivors and tracked objective values (elementwise
    math, only partitioned)."""
    _run("""
        import numpy as np
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        from repro.core.perfmodel import AccelWorkload, SoCPerfModel
        from repro.core.dse import grid_sweep

        model = SoCPerfModel()
        wls = (AccelWorkload("gsm", 4.61, 12.0),
               AccelWorkload("dfmul", 8.70, 1.1))
        kw = dict(ks=(1, 2, 4), acc_rates=(0.2, 0.6, 1.0),
                  noc_rates=(0.1, 0.5, 1.0), tg_rates=(0.5, 1.0), n_tg=2,
                  island_rates="independent",
                  chunk_points=700)     # not a device multiple: padding
        r1 = grid_sweep(model, wls, devices=1, **kw)
        r4 = grid_sweep(model, wls, devices=4, **kw)
        assert np.array_equal(r1.pareto, r4.pareto)
        assert np.array_equal(r1.cand_indices, r4.cand_indices)
        for o in r1.topk:
            assert np.array_equal(r1.topk[o], r4.topk[o]), o
        for o, v in r1.cand_values.items():
            assert np.array_equal(v, r4.cand_values[o]), o

        # dense (unchunked) path shards too
        d1 = grid_sweep(model, wls, devices=1, **{**kw, "chunk_points": None})
        d4 = grid_sweep(model, wls, devices=4, **{**kw, "chunk_points": None})
        for f in ("throughput", "energy_per_unit", "mem_traffic"):
            assert np.array_equal(getattr(d1, f), getattr(d4, f)), f
        assert np.array_equal(d1.pareto_indices(), d4.pareto_indices())
        print("sweep invariance ok", len(r1.pareto))
    """)


def test_shard_cosim_invariance_1_vs_4_devices():
    """BatchSimEngine(jax, devices=4) == devices=None bitwise across
    open-loop and controlled runs (B=5: padding to 8 is exercised)."""
    _run("""
        import numpy as np
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        from repro.core.dfs import BatchMemoryBoundPolicy, BatchPIDRatePolicy
        from repro.sim import BatchSimEngine, BatchSimPlatform, SimConfig, SimEngine
        from test_sim_batch import batch_controller, make_platform, make_trace

        POL = {"open": None,
               "membound": lambda: BatchMemoryBoundPolicy(threshold=0.5),
               "pid": lambda: BatchPIDRatePolicy(target=0.7)}
        cap = SimEngine(make_platform(4, k=2)).capacity_rps()
        tr = make_trace("diurnal", cap, ticks=300, n=4)

        def run(devices, key):
            plats = [make_platform(4, k=k) for k in (2, 2, 4, 8, 8)]
            bplat = BatchSimPlatform.stack(plats)
            pf = POL[key]
            ctl = (None if pf is None else
                   batch_controller(bplat, pf(), queue_guard_ticks=3.0))
            eng = BatchSimEngine(bplat, config=SimConfig(control_interval=25),
                                 controller=ctl, backend="jax",
                                 devices=devices)
            return eng.run(tr)

        for key in POL:
            a, b = run(None, key), run(4, key)
            for f in ("completed", "energy_j", "residual", "swaps",
                      "p99_latency_s"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                    err_msg=f"{key}:{f}")
        print("cosim invariance ok")
    """)


def test_shard_closed_loop_score_forwarding():
    """closed_loop_score(devices=) reaches the batched engine: sharded
    scoring reproduces single-device scoring bitwise."""
    _run("""
        import numpy as np
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        from repro.core.dse import closed_loop_score, grid_sweep
        from repro.core.perfmodel import AccelWorkload, SoCPerfModel
        from repro.sim import diurnal_trace

        m = SoCPerfModel()
        wls = [AccelWorkload("dfadd", 9.22, 0.9),
               AccelWorkload("dfmul", 8.70, 1.1)]
        res = grid_sweep(m, wls, ks=(1, 2, 4), acc_rates=(0.2, 0.6, 1.0),
                         noc_rates=(0.5, 1.0), n_tg=2)
        idx = res.topk_indices(6)
        tr = diurnal_trace(2000.0, 250, 2, dt=1e-3, seed=5)
        kw = dict(model=m, indices=idx, req_mb=0.002, backend="jax")
        s1 = closed_loop_score(res, tr, devices=None, **kw)
        s4 = closed_loop_score(res, tr, devices=4, **kw)
        np.testing.assert_array_equal(s1.p99_latency_s, s4.p99_latency_s)
        np.testing.assert_array_equal(s1.energy_per_request_j,
                                      s4.energy_per_request_j)
        np.testing.assert_array_equal(s1.ranked_indices(),
                                      s4.ranked_indices())
        print("closed-loop forwarding ok")
    """)
