"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference (:mod:`reference`), one number each.

Every number is 0 when the two agree exactly and grows with the gap; a
run is correct when each number is at or below its limit
(``limits/<cell>.json``).
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

from perfbench import reference as ref


def rel_err(got, want) -> float:
    """Largest |got - want| / |want|; inf where exactly one is NaN."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    nan_g, nan_w = np.isnan(got), np.isnan(want)
    if (nan_g != nan_w).any():
        return math.inf
    ok = ~nan_w
    d = np.abs(got[ok] - want[ok])
    scale = np.abs(want[ok])
    err = np.where(d == 0, 0.0, d / np.where(scale > 0, scale, 1e-300))
    return float(err.max()) if err.size else 0.0


def _distance(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n,) distance of each row of ``a`` to its nearest row of ``b``:
    the largest relative gap over the objectives."""
    if b.shape[0] == 0:
        return np.full(a.shape[0], math.inf)
    gap = np.abs(a[:, None, :] - b[None, :, :]) / np.maximum(
        np.abs(b[None, :, :]), 1e-300)
    return gap.max(axis=-1).min(axis=1)


def sweep_numbers(cfg: dict, got: dict, want: dict) -> Dict[str, float]:
    """``got``: the program's sweep as plain arrays (``n_points``,
    ``n_valid``, ``pareto``, ``topk`` per objective, ``indices`` and
    ``values`` of every point it kept).  ``want``: :func:`reference.sweep`.

    * ``count_err``: points and valid points miscounted;
    * ``value_err``: largest relative error of any objective at any point
      the program kept, against the reference's value at that point;
    * ``topk_gap``: largest relative gap, rank by rank, between the
      reference's values at the program's top-k and at its own top-k;
    * ``front_gap``: every point in one front and not the other lies
      within this relative gap, objective by objective, of a point of the
      other front (reference values; 0 when the fronts are equal)."""
    out = {"count_err": float(abs(got["n_points"] - want["n_points"])
                              + abs(got["n_valid"] - want["n_valid"]))}
    kept = np.asarray(got["indices"], dtype=np.int64)
    at_kept = ref.point_objectives(cfg, kept)
    out["value_err"] = max(
        rel_err(got["values"][o], np.broadcast_to(at_kept[o], kept.shape))
        for o, _ in ref.OBJECTIVES)
    gap = 0.0
    for o, _ in ref.OBJECTIVES:
        mine = np.asarray(got["topk"][o], dtype=np.int64)
        best = want["topk"][o]
        if mine.shape != best.shape:
            gap = math.inf
            break
        gap = max(gap, rel_err(np.broadcast_to(
            ref.point_objectives(cfg, mine)[o], mine.shape),
            np.broadcast_to(ref.point_objectives(cfg, best)[o], best.shape)))
    out["topk_gap"] = gap
    p = np.asarray(got["pareto"], dtype=np.int64)
    r = want["pareto"]
    only_p, only_r = np.setdiff1d(p, r), np.setdiff1d(r, p)

    def objs(idx):
        ob = ref.point_objectives(cfg, idx)
        return np.stack([np.broadcast_to(ob[o], idx.shape) for o in
                         ("throughput", "area", "energy_per_unit")], -1)

    d = [0.0]
    if only_p.size:
        d.append(float(_distance(objs(only_p), objs(r)).max()))
    if only_r.size:
        d.append(float(_distance(objs(only_r), objs(p)).max()))
    out["front_gap"] = max(d)
    return out


def rank_inversion(order, miss, ept, bad) -> float:
    """How far the given best-first ``order`` departs from the reference's
    ranking keys: over every pair ranked i before j, the relative amount
    by which i's key exceeds j's (the SLO miss first; energy per request
    where the misses are equal); inf when a design that completed
    nothing is ranked before one that did."""
    order = np.asarray(order, dtype=np.int64)
    B = order.size
    if sorted(order.tolist()) != list(range(B)):
        return math.inf
    m, e, b = (np.asarray(x)[order] for x in (miss, ept, bad))
    later = np.triu(np.ones((B, B), dtype=bool), 1)        # i before j
    if (later & b[:, None] & ~b[None, :]).any():
        return math.inf
    ok = ~b[:, None] & ~b[None, :] & later
    mi, mj = m[:, None], m[None, :]
    ei, ej = e[:, None], e[None, :]
    miss_gap = np.where(mi > mj, (mi - mj) / np.maximum(np.abs(mi), 1e-300),
                        0.0)
    ept_gap = np.where((mi == mj) & (ei > ej),
                       (ei - ej) / np.maximum(np.abs(ei), 1e-300), 0.0)
    amount = np.where(ok, np.maximum(miss_gap, ept_gap), 0.0)
    return float(amount.max()) if amount.size else 0.0


def cosim_numbers(got: dict, want_sim: dict, want: dict,
                  faulted: bool) -> Dict[str, float]:
    """``got``: one ranking of the program as plain arrays (``completed``,
    ``energy``, ``p99``, ``swaps``, ``drop_rate``, ``order``);
    ``want_sim``/``want``: :func:`reference.cosim` and
    :func:`reference.score` on the same designs and trace.

    ``completed_err``, ``energy_err``, ``p99_err``: largest relative
    error over the designs; ``swap_diff``: designs whose count of DFS
    commits differs; ``drop_err``: largest absolute error of the drop
    rate (faulted runs); ``rank_inversion``: see :func:`rank_inversion`."""
    out = {"completed_err": rel_err(got["completed"], want_sim["completed"]),
           "energy_err": rel_err(got["energy"], want_sim["energy"]),
           "p99_err": rel_err(got["p99"], want["p99"]),
           "swap_diff": float(np.sum(np.asarray(got["swaps"])
                                     != want_sim["swaps"]))}
    if faulted:
        d = np.abs(np.asarray(got["drop_rate"], dtype=np.float64)
                   - want["drop_rate"])
        out["drop_err"] = float(d.max()) if d.size else 0.0
    out["rank_inversion"] = rank_inversion(got["order"], want["miss"],
                                           want["ept"], want["bad"])
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, dict]:
    """Each number that has a limit, beside its limit.  A limits file
    names every number it compares and nothing else."""
    unknown = sorted(set(limits) - set(numbers))
    if unknown:
        raise KeyError(f"limits for numbers never computed: {unknown}")
    return {k: {"value": float(numbers[k]), "limit": float(limits[k]),
                "ok": bool(numbers[k] <= limits[k])} for k in limits}


def worst(a: Optional[Dict[str, float]], b: Dict[str, float]):
    """Elementwise maximum of two sets of numbers."""
    if a is None:
        return dict(b)
    return {k: max(a.get(k, -math.inf), v) for k, v in b.items()}
