#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \\
        [--program] [--control]

For every seed, ``--program`` compares what the timed path produces (one
sweep, or one ranking per trace of the seed's pool) with the float64
reference: the lower readings.  ``--control`` puts the reference itself,
computed with every intermediate rounded to bfloat16, in the program's
place: the upper readings, which have to fail the limits.  One JSON line
per seed and side, then the largest reading of each number per side.
Runs on the chip like ``run.py`` (``--program``), and needs no chip for
``--control`` alone.
"""
import argparse
import json
import os
import sys
import time
import types

import numpy as np


def sweep_readings(cfg, traffic, prec, work=None):
    from perfbench import compare
    from perfbench import reference as ref
    from perfbench.kinds import sweep
    k = int(traffic["topk_track"])
    want = ref.sweep(cfg, k)
    if work is None:
        low = ref.sweep(cfg, k, prec)
        got = {"n_points": low["n_points"], "n_valid": low["n_valid"],
               "pareto": low["pareto"], "topk": low["topk"],
               "indices": low["indices"], "values": low["values"]}
    else:
        work.job(0)
        got = work.results[0]
    return compare.sweep_numbers(cfg, got, want)


def cosim_readings(work, seed, prec, program: bool):
    from perfbench import compare
    from perfbench import reference as ref
    work.pool = work.make_pool(seed)
    out = []
    for i, arrivals in enumerate(work.pool):
        sim, sc = work.reference(arrivals)
        if program:
            work.job(i)
            got = work.outputs[i]
        else:
            lsim, lsc = work.reference(arrivals, prec)
            got = {"completed": lsim["completed"], "energy": lsim["energy"],
                   "p99": lsc["p99"], "swaps": lsim["swaps"],
                   "drop_rate": lsc["drop_rate"], "order": lsc["order"]}
        out.append(compare.cosim_numbers(got, sim, sc, work.faulted))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.makedirs(os.path.join(root, ".jax_cache"), exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    sys.path[:0] = [root, os.path.join(root, "src")]
    from perfbench import compare, harness
    from perfbench import reference as ref
    p = harness.plan(root, args.workload)
    if args.program:
        print(json.dumps({"device": harness.look_for_chip(
            int(p.cell["chips"]), p.peaks)}), flush=True)
        from repro.shard import enable_compile_cache
        enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    sides = [s for s, on in (("program", args.program),
                             ("control", args.control)) if on]
    top = {s: None for s in sides}
    kind = p.traffic["kind"]
    work = None
    if kind == "cosim" or args.program:
        work = p.kind.Workload(types.SimpleNamespace(
            cfg=p.cfg, traffic=p.traffic, seed=seeds[0]))
        work.setup()
    for seed in seeds:
        for side in sides:
            t0 = time.perf_counter()
            if kind == "sweep":
                rows = [sweep_readings(p.cfg, p.traffic, ref.BF16,
                                       work if side == "program" else None)]
            else:
                rows = cosim_readings(work, seed, ref.BF16,
                                      side == "program")
            for r in rows:
                top[side] = compare.worst(top[side], r)
            print(json.dumps({"seed": seed, "side": side, "readings": rows,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    print(json.dumps({"workload": args.workload, "largest": top}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
