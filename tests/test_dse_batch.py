"""Scalar <-> batched parity for the vectorized DSE engine.

Property-style tests (seeded rng always; hypothesis variants when it is
installed) asserting that

* ``accel_throughput_batch`` / ``memory_traffic_batch`` match the scalar
  methods across random Ks, rates, placements and NoC configs (incl. torus),
* the O(N log N) Pareto front matches the O(N^2) brute force, including
  tie-heavy integer-valued objectives,
* ``grid_sweep`` reproduces ``sweep_soc`` point for point,
* the batched NoC routing tables match per-call route walks.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.dfs import policy_energy_per_token_sweep
from repro.core.dse import (DesignPoint, grid_sweep, pareto_front,
                            pareto_front_bruteforce, pareto_front_indices,
                            sweep_soc)
from repro.core.islands import (IslandConfig, IslandSpec, NOC_LADDER,
                                TILE_LADDER)
from repro.core.noc import (Flow, NocConfig, NocModel, hops, hops_batch,
                            link_loads_batch, positions_to_indices,
                            route_max_utilization, routing_tables, xy_route)
from repro.core.perfmodel import (AccelWorkload, SoCPerfModel, _throughput_math,
                                  chip_power)

NOCS = [NocConfig(4, 4), NocConfig(4, 4, torus=True),
        NocConfig(3, 5), NocConfig(5, 3, torus=True)]


def _rand_pos(rng, cfg):
    return (int(rng.integers(cfg.rows)), int(rng.integers(cfg.cols)))


# --------------------------------------------------------------- NoC tables
@pytest.mark.parametrize("cfg", NOCS, ids=lambda c: f"{c.rows}x{c.cols}"
                         + ("t" if c.torus else "m"))
def test_hop_matrix_matches_scalar_hops(cfg):
    t = routing_tables(cfg)
    n = cfg.rows * cfg.cols
    for s in range(n):
        for d in range(n):
            sp = (s // cfg.cols, s % cfg.cols)
            dp = (d // cfg.cols, d % cfg.cols)
            assert t.hop_matrix[s, d] == hops(cfg, sp, dp)
            assert t.hop_matrix[s, d] == len(xy_route(cfg, sp, dp))


@pytest.mark.parametrize("cfg", NOCS[:2], ids=["mesh", "torus"])
def test_link_loads_batch_matches_nocmodel(cfg):
    rng = np.random.default_rng(3)
    flows = [Flow(_rand_pos(rng, cfg), _rand_pos(rng, cfg),
                  float(rng.random())) for _ in range(64)]
    scalar = NocModel(cfg)
    for f in flows:
        scalar.add_flow(f)
    batched = NocModel(cfg)
    batched.add_flows(flows)
    t = routing_tables(cfg)
    loads = link_loads_batch(
        cfg, positions_to_indices(cfg, [f.src for f in flows]),
        positions_to_indices(cfg, [f.dst for f in flows]),
        [f.bytes_per_cycle for f in flows])
    for i, link in enumerate(t.links):
        assert loads[i] == pytest.approx(scalar.link_load.get(link, 0.0))
        assert batched.link_load.get(link, 0.0) == pytest.approx(
            scalar.link_load.get(link, 0.0))


@pytest.mark.parametrize("cfg", NOCS[:2], ids=["mesh", "torus"])
def test_slowdown_batch_matches_scalar(cfg):
    rng = np.random.default_rng(4)
    m = NocModel(cfg)
    m.add_flows([Flow(_rand_pos(rng, cfg), _rand_pos(rng, cfg),
                      float(rng.random())) for _ in range(32)])
    pairs = [(_rand_pos(rng, cfg), _rand_pos(rng, cfg)) for _ in range(40)]
    pairs.append(((1, 1), (1, 1)))                       # zero-hop route
    sb = m.slowdown_batch(
        positions_to_indices(cfg, [p[0] for p in pairs]),
        positions_to_indices(cfg, [p[1] for p in pairs]))
    for i, (s, d) in enumerate(pairs):
        assert sb[i] == pytest.approx(m.slowdown(s, d), rel=1e-12)


def test_xy_route_returns_fresh_list():
    cfg = NocConfig(4, 4)
    r1 = xy_route(cfg, (0, 0), (2, 2))
    r1.append("sentinel")
    assert "sentinel" not in xy_route(cfg, (0, 0), (2, 2))


# ------------------------------------------------------- perf-model parity
@pytest.mark.parametrize("torus", [False, True], ids=["mesh", "torus"])
def test_throughput_batch_matches_scalar_random(torus):
    rng = np.random.default_rng(5)
    m = SoCPerfModel(noc=NocConfig(4, 4, torus=torus))
    names = list(("adpcm", "dfadd", "dfmul", "dfsin", "gsm"))
    B = 300
    ks = rng.choice([1, 2, 4, 8], B)
    fa = rng.uniform(0.05, 1.0, B)
    fn = rng.uniform(0.05, 1.0, B)
    ft = rng.uniform(0.1, 1.0, B)
    ntg = rng.integers(0, 12, B)
    pos = np.stack([rng.integers(0, 4, B), rng.integers(0, 4, B)], axis=-1)
    for name in names:
        wl = AccelWorkload(name, 4.61, 12.0)
        batch = m.accel_throughput_batch(
            base_mbps=wl.base_mbps, wire_share=wl.wire_share, k=ks,
            f_acc=fa, f_noc=fn, f_tg=ft, n_tg=ntg,
            pos_idx=positions_to_indices(m.noc, pos))
        for i in range(0, B, 17):                        # spot-check sample
            w = AccelWorkload(name, wl.base_mbps, wl.ai,
                              replication=int(ks[i]))
            s = m.accel_throughput(
                w, (int(pos[i, 0]), int(pos[i, 1])),
                {"acc": float(fa[i]), "noc_mem": float(fn[i]),
                 "tg": float(ft[i])}, int(ntg[i]))
            assert batch[i] == pytest.approx(s, rel=1e-6)


def test_throughput_jax_backend_close_to_numpy():
    """The throughput math the device evaluator runs, jitted over
    jax.numpy, against the float64 NumPy batch path."""
    import jax
    import jax.numpy as jnp

    m = SoCPerfModel()
    ks = np.array([1.0, 2.0, 4.0])[:, None]
    fa = np.array([0.2, 0.6, 1.0])[None, :]
    a = m.accel_throughput_batch(base_mbps=4.61, wire_share=0.035, k=ks,
                                 f_acc=fa, f_noc=0.5, f_tg=1.0, n_tg=4,
                                 pos=(3, 3))
    consts = dict(own_demand=m.own_demand, tg_demand=m.tg_demand,
                  link_bw=m.noc.link_bw,
                  hop_latency_share=m.hop_latency_share,
                  ref_hops=float(m._ref_hops()))
    kern = jax.jit(lambda *arrs: _throughput_math(jnp, *arrs, **consts))
    b = np.asarray(kern(4.61, 0.035, ks, fa, 0.5, 1.0, 4,
                        m.hop_counts(pos=(3, 3))))
    # jax default precision is float32 unless jax_enable_x64
    np.testing.assert_allclose(b, a, rtol=1e-5)


def test_memory_traffic_batch_matches_scalar():
    m = SoCPerfModel()
    rng = np.random.default_rng(6)
    for _ in range(100):
        rates = {"acc": float(rng.uniform(0, 1)),
                 "noc_mem": float(rng.uniform(0.05, 1)),
                 "tg": float(rng.uniform(0, 1))}
        n_tg = int(rng.integers(0, 12))
        n_acc = int(rng.integers(0, 4))
        s = m.memory_traffic_mpkts(rates, n_tg, [(1, 1)] * n_acc)
        b = float(m.memory_traffic_batch(
            f_acc=rates["acc"], f_noc=rates["noc_mem"], f_tg=rates["tg"],
            n_tg=n_tg, n_accels=n_acc))
        assert b == pytest.approx(s, rel=1e-9)


# ------------------------------------------------------------ Pareto front
def _front_keys(points):
    return sorted((p.throughput, p.area, p.energy_per_unit) for p in points)


def test_pareto_fast_matches_bruteforce_ties():
    rng = np.random.default_rng(7)
    for _ in range(25):
        n = int(rng.integers(1, 400))
        # integer-quantized objectives force heavy ties and duplicates
        thr = rng.integers(0, 10, n).astype(float)
        area = rng.integers(0, 6, n).astype(float)
        en = rng.integers(0, 6, n).astype(float)
        pts = [DesignPoint({}, {}, {}, thr[i], area[i], en[i])
               for i in range(n)]
        bf = pareto_front_bruteforce(pts)
        idx = pareto_front_indices(thr, area, en)
        assert sorted(map(id, bf)) == sorted(id(pts[i]) for i in idx)


def test_pareto_fast_matches_bruteforce_continuous():
    rng = np.random.default_rng(8)
    n = 1000
    thr, area, en = rng.random(n), rng.random(n), rng.random(n)
    pts = [DesignPoint({}, {}, {}, thr[i], area[i], en[i]) for i in range(n)]
    bf = pareto_front_bruteforce(pts)
    idx = pareto_front_indices(thr, area, en)
    assert sorted(map(id, bf)) == sorted(id(pts[i]) for i in idx)


def test_pareto_public_api_uses_fast_path():
    m = SoCPerfModel()
    pts = sweep_soc(m, AccelWorkload("gsm", 4.61, 12.0), n_tg=4)
    assert {p.key() for p in pareto_front(pts)} == {
        p.key() for p in pareto_front_bruteforce(pts)}


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_pareto_fast_matches_bruteforce_hypothesis(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 120))
    thr = rng.integers(0, 8, n).astype(float)
    area = rng.integers(0, 5, n).astype(float)
    en = rng.integers(0, 5, n).astype(float)
    pts = [DesignPoint({}, {}, {}, thr[i], area[i], en[i]) for i in range(n)]
    bf = pareto_front_bruteforce(pts)
    idx = pareto_front_indices(thr, area, en)
    assert sorted(map(id, bf)) == sorted(id(pts[i]) for i in idx)


# -------------------------------------------------------------- grid sweep
@pytest.mark.parametrize("torus", [False, True], ids=["mesh", "torus"])
def test_grid_sweep_matches_sweep_soc(torus):
    m = SoCPerfModel(noc=NocConfig(4, 4, torus=torus))
    wl = AccelWorkload("dfmul", 8.70, 1.1)
    kw = dict(ks=(1, 2, 4), noc_rates=(0.1, 0.5, 1.0),
              acc_rates=(0.2, 0.6, 1.0), positions=((1, 1), (3, 3)))
    scalar = {p.key(): p for p in sweep_soc(m, wl, n_tg=4, **kw)}
    res = grid_sweep(m, wl, tg_rates=(1.0,), n_tg=4, **kw)
    assert len(res) == len(scalar)
    for i in range(len(res)):
        dp = res.design_point(i)
        sp = scalar[dp.key()]
        assert dp.throughput == pytest.approx(sp.throughput, rel=1e-6)
        assert dp.area == pytest.approx(sp.area, rel=1e-6)
        assert dp.energy_per_unit == pytest.approx(sp.energy_per_unit,
                                                   rel=1e-6)


def test_grid_sweep_joint_masks_collisions():
    m = SoCPerfModel()
    wls = [AccelWorkload("dfsin", 0.33, 60.0),
           AccelWorkload("gsm", 4.61, 12.0)]
    res = grid_sweep(m, wls, ks=(1, 2), acc_rates=(1.0,), noc_rates=(1.0,),
                     positions=((1, 1), (3, 3), (0, 2)), n_tg=0)
    assert len(res) == 2 * 2 * 3 * 3
    # exactly the same-position placements are invalid
    assert res.n_valid == 2 * 2 * (3 * 3 - 3)
    for i in res.pareto_indices():
        dp = res.design_point(int(i))
        assert dp.placement["dfsin"] != dp.placement["gsm"]
    # joint throughput == sum of per-accel scalar throughputs
    i = int(res.topk_indices(1)[0])
    dp = res.design_point(i)
    expect = sum(
        m.accel_throughput(
            AccelWorkload(w.name, w.base_mbps, w.ai,
                          replication=dp.replication[w.name]),
            dp.placement[w.name], dp.rates, 0)
        for w in wls)
    assert dp.throughput == pytest.approx(expect, rel=1e-6)


def test_grid_sweep_mem_traffic_matches_scalar():
    """SweepResult.mem_traffic reproduces the scalar Fig.-4 model at the
    axis values of each flat point."""
    m = SoCPerfModel()
    wls = [AccelWorkload("dfsin", 0.33, 60.0),
           AccelWorkload("gsm", 4.61, 12.0)]
    res = grid_sweep(m, wls, ks=(1, 2), acc_rates=(0.2, 1.0),
                     noc_rates=(0.5, 1.0), tg_rates=(0.5, 1.0),
                     positions=((1, 1), (3, 3)), n_tg=6)
    assert res.mem_traffic is not None
    assert res.mem_traffic.shape == res.throughput.shape
    rng = np.random.default_rng(11)
    for i in rng.integers(0, len(res), 40):
        av = res.axis_values(int(i))
        want = m.memory_traffic_mpkts(
            {"acc": av["f_acc"], "noc_mem": av["f_noc"], "tg": av["f_tg"]},
            res.n_tg, [(0, 0)] * len(wls))
        assert res.mem_traffic[int(i)] == pytest.approx(want, rel=1e-12)
    # usable as a topk objective like any other array
    low = res.topk_indices(3, objective="mem_traffic", maximize=False)
    assert res.mem_traffic[low][0] == res.mem_traffic[res.valid].min()


def test_grid_sweep_topk_sorted_and_valid():
    m = SoCPerfModel()
    res = grid_sweep(m, AccelWorkload("gsm", 4.61, 12.0),
                     ks=(1, 2, 4), acc_rates=TILE_LADDER.levels(),
                     noc_rates=NOC_LADDER.levels(), n_tg=2)
    top = res.topk_indices(20)
    vals = res.throughput[top]
    assert np.all(np.diff(vals) <= 1e-12)
    assert np.all(res.valid[top])
    assert vals[0] == res.throughput[res.valid].max()
    low = res.topk_indices(5, objective="energy_per_unit")
    assert res.energy_per_unit[low][0] == res.energy_per_unit[res.valid].min()


# ------------------------------------------------------------- DFS policy
def test_policy_energy_sweep_feasible_and_on_ladder():
    m = SoCPerfModel()
    wl = AccelWorkload("dfmul", 8.70, 1.1, replication=4)
    islands = IslandConfig((
        IslandSpec("acc", ("A2",), TILE_LADDER, 1.0),
        IslandSpec("noc_mem", ("NOC", "MEM"), NOC_LADDER, 1.0)))

    def eval_batch(rates):
        fa, fn = rates["acc"], rates["noc_mem"]
        tps = m.accel_throughput_batch(
            base_mbps=wl.base_mbps, wire_share=wl.wire_share,
            k=wl.replication, f_acc=fa, f_noc=fn, f_tg=1.0, n_tg=4,
            pos=(3, 3))
        watts = chip_power(fa, 1.0) + 0.3 * chip_power(fn, 1.0)
        return tps, np.broadcast_to(watts, np.shape(tps))

    best = policy_energy_per_token_sweep(islands, eval_batch, max_loss=0.3)
    assert set(best) == {"acc", "noc_mem"}
    assert best["acc"] in TILE_LADDER.levels()
    assert best["noc_mem"] in NOC_LADDER.levels()
    # constraint respected: chosen tps within 30% of all-max tps
    tps_best, _ = eval_batch({k: np.asarray([v]) for k, v in best.items()})
    tps_max, _ = eval_batch({"acc": np.asarray([1.0]),
                             "noc_mem": np.asarray([1.0])})
    assert float(tps_best[0]) >= 0.7 * float(tps_max[0])
