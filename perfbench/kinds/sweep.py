"""Sweep cells: whole ``grid_sweep`` runs of a configuration's design
space, back to back, each to a finished Pareto front and top-k.

Traffic file keys: ``chunk_points``, ``topk_track``, ``devices``,
``warmup`` (a list of overrides of the space, such as ``{"ks": [1]}``,
whose sweeps compile every chunk shape the full sweep uses) and
``traced_jobs``.  The seed changes nothing here: the space is the input.
"""
from __future__ import annotations

import hashlib

import numpy as np

from perfbench import compare, program
from perfbench import reference as ref

END_TO_END = "sweep_points_per_s"


def _plain(res, k: int) -> dict:
    """The program's sweep result as plain arrays."""
    objs = [o for o, _ in ref.OBJECTIVES]
    if hasattr(res, "cand_indices"):
        return {"n_points": int(res.n_points), "n_valid": int(res.n_valid),
                "pareto": np.asarray(res.pareto),
                "topk": {o: np.asarray(res.topk[o]) for o in objs},
                "indices": np.asarray(res.cand_indices),
                "values": {o: np.asarray(res.cand_values[o]) for o in objs}}
    top = {o: np.asarray(res.topk_indices(k, o, mx)) for o, mx
           in ref.OBJECTIVES}
    front = np.asarray(res.pareto_indices())
    idx = np.unique(np.concatenate([front] + list(top.values())))
    return {"n_points": len(res), "n_valid": int(res.n_valid),
            "pareto": front, "topk": top, "indices": idx,
            "values": {o: np.asarray(res.objective_values(o, idx))
                       for o in objs}}


def _digest(p: dict) -> str:
    h = hashlib.sha256()
    for a in ([p["pareto"], p["indices"]] + [p["topk"][o] for o in
                                             sorted(p["topk"])]
              + [p["values"][o] for o in sorted(p["values"])]):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Workload:
    def __init__(self, ctx):
        self.cfg, self.traffic, self.seed = ctx.cfg, ctx.traffic, ctx.seed
        self.A = len(self.cfg["accelerators"])
        self.k = int(self.traffic["topk_track"])
        self.results = {}
        self.traced_jobs = int(self.traffic.get("traced_jobs", 1))
        self.warmup_jobs = 0        # set-up's cut sweeps warm every shape

    def _kwargs(self, **override):
        kw = program.sweep_kwargs(self.cfg)
        kw.update(chunk_points=int(self.traffic["chunk_points"]),
                  topk_track=self.k, devices=self.traffic["devices"])
        kw.update(override)
        return kw

    def setup(self):
        from repro.core.dse import grid_sweep
        self._grid_sweep = grid_sweep
        self.model, self.wls = program.model_and_workloads(self.cfg)
        for over in self.traffic.get("warmup", ()):
            grid_sweep(self.model, self.wls, **self._kwargs(**{
                k: tuple(v) if isinstance(v, list) else v
                for k, v in over.items()}))

    def job(self, i: int) -> dict:
        from repro.sim.observe import get_profiler, reset_profiler
        reset_profiler()
        res = self._grid_sweep(self.model, self.wls, **self._kwargs())
        chunk = get_profiler().summary().get("sweep_chunk", {})
        p = _plain(res, self.k)
        self.results[i] = p
        return {"work": p["n_points"], "chunk_s": chunk.get("total_s", 0.0),
                "chunks": chunk.get("count", 0), "digest": _digest(p)}

    def end_to_end(self, jobs, window_s: float) -> dict:
        return {END_TO_END: sum(j["work"] for j in jobs) / window_s}

    def shape(self) -> dict:
        return {"accels": self.A}

    def release(self):
        self.model = self.wls = self._grid_sweep = None

    def check(self, jobs) -> dict:
        want = ref.sweep(self.cfg, self.k)
        numbers = compare.sweep_numbers(
            self.cfg, self.results[jobs[-1]["i"]], want)
        numbers["sweeps_differ"] = float(sum(
            j["digest"] != jobs[-1]["digest"] for j in jobs))
        return numbers
