#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: grid_sweep -> closed_loop_score
-> BatchSimEngine, at the paper's design size, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the multi-chip path

Everything runs on the paper's 4x4 SoC with CHStone data
(``repro.configs.vespa_soc``).  With one chip:

* sweep: the per-island space of dfadd+dfmul+dfsin (20,194,758 points
  in 2M-point chunks) through ``grid_sweep(devices=1)``, the jitted
  evaluator on the chip, checked against the NumPy float64 sweep on the
  host: the same top-1 design, and throughput, energy and memory traffic
  of the top 512 within ``SWEEP_RTOL``;
* co-sim: ``closed_loop_score`` of those 512 survivors under a seeded
  diurnal trace with PID DFS and a queue guard, on ``backend="jax"``
  (the ``lax.scan`` tick loop) and ``backend="pallas"`` (the fused tick
  kernel, compiled), each checked against ``backend="numpy"`` (float64):
  completed, energy and p99 within ``COSIM_RTOL``, swap counts equal,
  and the ranking equal up to designs whose float64 scores lie closer
  than that tolerance (see :func:`check_ranking`).

``--chips 4`` runs the same sweep and the jax co-sim at ``devices=4``
and checks them bitwise against ``devices=1``.

The times printed are smoke timings of one run, compilation included;
they are not benchmark metrics.  The last line of stdout is one JSON
object naming the device; any failed check raises before it, and the
script exits non-zero when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager

import numpy as np

ACCELS = ("dfadd", "dfmul", "dfsin")
CHUNK_POINTS = 2_000_000
SURVIVORS = 512
TICKS = 4000
DT = 1e-3
MEAN_RPS = 2000.0
REQ_MB = 0.002
CONTROL_INTERVAL = 25
TRACE_SEED = 11
SWEEP_RTOL = 1e-5
COSIM_RTOL = 2e-3       # the fused kernel's stated f32 tolerance

_COMPILE = {"seconds": 0.0, "hits": 0, "misses": 0}


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _COMPILE["misses"] += 1


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += duration


def check(ok, *what):
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise AssertionError(" ".join(map(str, what)))


@contextmanager
def phase(name):
    """Print one phase's wall time and compile time (compile-or-load,
    from JAX's monitoring events) as a smoke timing."""
    before = dict(_COMPILE)
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    print(f"smoke timing (not a metric): phase={name} wall_s={wall:.3f} "
          f"compile_s={_COMPILE['seconds'] - before['seconds']:.3f} "
          f"cache_hits={_COMPILE['hits'] - before['hits']} "
          f"cache_misses={_COMPILE['misses'] - before['misses']}",
          flush=True)


def paper_sweep(devices, *, chunk_points=CHUNK_POINTS, ladders=None):
    """The per-island sweep of the paper SoC; ``ladders`` overrides the
    (accelerator, NoC) rate ladders, for a cut-down rehearsal."""
    from repro.configs.vespa_soc import CHSTONE
    from repro.core.dse import grid_sweep
    from repro.core.islands import NOC_LADDER, TILE_LADDER
    from repro.core.perfmodel import AccelWorkload, SoCPerfModel
    acc, noc = ladders or (TILE_LADDER.levels(), NOC_LADDER.levels())
    model = SoCPerfModel()
    wls = [AccelWorkload(n, *CHSTONE[n]) for n in ACCELS]
    res = grid_sweep(model, wls, ks=(1, 2, 4), acc_rates=acc,
                     noc_rates=noc, tg_rates=(0.5, 1.0),
                     positions=((1, 1), (3, 3), (0, 2)), n_tg=4,
                     island_rates="independent", chunk_points=chunk_points,
                     topk_track=SURVIVORS, devices=devices)
    return model, res


def check_sweep(dev, ref, k=SURVIVORS):
    """Top-1 identical; the top-``k`` objectives within ``SWEEP_RTOL``.
    Members of one top-k missing from the other may only be f32 near-ties
    at its boundary, so sorted throughputs are compared as well."""
    top_d, top_r = dev.topk_indices(k), ref.topk_indices(k)
    check(top_d[0] == top_r[0], "top-1 differs", top_d[0], top_r[0])
    common = np.intersect1d(top_d, top_r)
    worst = 0.0
    for obj in ("throughput", "energy_per_unit", "mem_traffic"):
        a = dev.objective_values(obj, common)
        b = ref.objective_values(obj, common)
        rel = float(np.max(np.abs(a - b) / np.abs(b)))
        check(rel <= SWEEP_RTOL, obj, rel)
        worst = max(worst, rel)
    a = np.sort(dev.objective_values("throughput", top_d))
    b = np.sort(ref.objective_values("throughput", top_r))
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    check(rel <= SWEEP_RTOL, "sorted top-k throughput", rel)
    print(f"sweep check: points={len(dev)} top1={int(top_d[0])} "
          f"top{k}_common={common.size} max_rel_err={max(worst, rel):.3e} "
          f"pareto dev={dev.pareto.size} ref={ref.pareto.size}", flush=True)


def cosim(res, model, indices, backend, *, devices=None, ticks=TICKS):
    """``closed_loop_score`` of ``indices`` under PID DFS + queue guard."""
    from repro.core.dfs import BatchPIDRatePolicy
    from repro.core.dse import closed_loop_score
    from repro.sim import BatchControllerHarness, SimConfig, diurnal_trace

    def pid_guard(p):
        return BatchControllerHarness(p.islands, p.rates,
                                      BatchPIDRatePolicy(target=0.7),
                                      tile_names=p.names,
                                      queue_guard_ticks=3.0)

    def trace(seed):
        return diurnal_trace(MEAN_RPS, ticks, len(ACCELS), dt=DT,
                             depth=0.4, seed=seed)

    return closed_loop_score(
        res, trace, model=model, indices=indices, req_mb=REQ_MB,
        sim_config=SimConfig(control_interval=CONTROL_INTERVAL),
        batch_controller_factory=pid_guard, backend=backend,
        trace_seed=TRACE_SEED, devices=devices)


def _stats(score):
    r = score.results[0]
    return {"completed": r.completed, "energy": r.energy_j,
            "p99": r.p99_latency_s, "swaps": r.swaps}


def check_cosim(name, got, ref):
    """f32 backend against the float64 reference."""
    g, r = _stats(got), _stats(ref)
    worst = {}
    for key in ("completed", "energy", "p99"):
        a, b = np.asarray(g[key]), np.asarray(r[key])
        check(np.all(np.isfinite(a)), name, key, "non-finite")
        np.testing.assert_allclose(a, b, rtol=COSIM_RTOL,
                                   err_msg=f"{name} {key}")
        worst[key] = float(np.max(np.abs(a - b) / np.abs(b)))
    np.testing.assert_array_equal(g["swaps"], r["swaps"],
                                  err_msg=f"{name} swaps")
    inversion, same = check_ranking(got, ref)
    print(f"co-sim check: backend={name} designs={len(got.indices)} "
          f"swaps_total={int(np.sum(g['swaps']))} "
          + " ".join(f"{k}_max_rel_err={v:.3e}" for k, v in worst.items())
          + f" ranking_same_position={same} "
          f"ranking_max_inversion={inversion:.3e}", flush=True)


def check_ranking(got, ref):
    """The f32 ranking must order the designs as the float64 one does,
    except where their float64 energies per request (the primary key)
    differ by less than ``COSIM_RTOL``: the reference holds distinct
    scores closer than f32 resolves, so an exact permutation match is
    not a property f32 can have.  Walking the f32 ranking, no design's
    float64 score may fall more than the tolerance below one ranked
    ahead of it.  Returns the largest such inversion and the count of
    designs at the same position."""
    check(np.array_equal(got.indices, ref.indices), "designs differ")
    seq = ref.energy_per_request_j[got.order]
    ahead = np.maximum.accumulate(seq)
    inversion = float(np.max((ahead - seq) / seq))
    check(inversion <= COSIM_RTOL, "ranking inversion", inversion)
    same = int(np.sum(got.order == ref.order))
    return inversion, same


def check_bitwise(name, a, b):
    """Two runs that differ only in device count."""
    for x, y, what in a:
        np.testing.assert_array_equal(x, y, err_msg=f"{name} {what}")
    print(f"bitwise check: {name} {len(a)} arrays equal at 1 and {b} "
          "devices", flush=True)


def one_chip():
    from repro.kernels.tick_sim import interpret_mode
    mode = "interpret" if interpret_mode() else "compiled"
    print(f"pallas: {mode}", flush=True)
    check(mode == "compiled", "the tick kernel must compile on a TPU")
    with phase("sweep_numpy_f64_host"):
        model, ref = paper_sweep(None)
    with phase("sweep_device"):
        _, dev = paper_sweep(1)
    check_sweep(dev, ref)
    survivors = dev.topk_indices(SURVIVORS)
    with phase("cosim_numpy_f64_host"):
        s_ref = cosim(dev, model, survivors, "numpy")
    for backend in ("jax", "pallas"):
        with phase(f"cosim_{backend}"):
            s = cosim(dev, model, survivors, backend)
        check_cosim(backend, s, s_ref)


def four_chips():
    with phase("sweep_devices_1"):
        model, r1 = paper_sweep(1)
    with phase("sweep_devices_4"):
        _, r4 = paper_sweep(4)
    pairs = [(r1.pareto, r4.pareto, "pareto"),
             (r1.cand_indices, r4.cand_indices, "tracked indices")]
    pairs += [(r1.topk[k], r4.topk[k], f"top-k {k}") for k in r1.topk]
    pairs += [(r1.cand_values[k], r4.cand_values[k], f"values {k}")
              for k in r1.cand_values]
    check_bitwise("sweep", pairs, 4)
    survivors = r1.topk_indices(SURVIVORS)
    with phase("cosim_jax_devices_1"):
        s1 = cosim(r1, model, survivors, "jax", devices=1)
    with phase("cosim_jax_devices_4"):
        s4 = cosim(r1, model, survivors, "jax", devices=4)
    st1, st4 = _stats(s1), _stats(s4)
    pairs = [(st1[k], st4[k], k) for k in st1]
    pairs += [(s1.energy_per_request_j, s4.energy_per_request_j, "ept"),
              (s1.ranked_indices(), s4.ranked_indices(), "ranking")]
    check_bitwise("co-sim jax", pairs, 4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip path against 1 chip")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.shard import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)
    print(f"compile cache: {cache}", flush=True)

    with phase("total"):
        if args.chips == 4:
            four_chips()
        else:
            one_chip()

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
