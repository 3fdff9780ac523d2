"""Design-space exploration driver — what Vespa exists for.

Sweeps the paper's three design axes and reports Pareto-optimal points:

* replication K per accelerator tile    (C1),
* per-island rate assignment            (C2),
* tile placement on the NoC grid        (Fig. 2's A1-near vs A2-far).

Two performance models: the analytic :class:`SoCPerfModel` (fast, used for
sweeps and the paper-claims tests) and the dry-run roofline
(launch/dryrun.py), used to validate chosen points against compiled HLO.

:func:`grid_sweep` has two evaluation paths, chosen by ``devices=`` alone:
``None`` runs the float64 NumPy reference, an int or ``"auto"`` runs the
flat-point jax evaluator on that many devices.

Two evaluation *shapes*:

* :func:`sweep_soc` — the original scalar ``itertools.product`` loop.  It
  builds a :class:`DesignPoint` per point and is kept as the slow,
  obviously-correct reference the batched engine is tested against.
* :func:`grid_sweep` — the batched array program.  It materializes the
  full cross-product (joint multi-accelerator K ladders x island-rate
  ladders x all grid placements) as broadcast axes, pushes the whole grid
  through ``SoCPerfModel.accel_throughput_batch`` in one vectorized call,
  and returns a :class:`SweepResult` of flat objective arrays, with no
  per-point Python objects.  DesignPoints are materialized lazily
  (:meth:`SweepResult.design_point`) only for the handful of survivors
  (Pareto front / top-k).

The Pareto front is sort-based O(N log N) (:func:`pareto_front_indices`);
the O(N^2) brute force survives as :func:`pareto_front_bruteforce` for
verification.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from bisect import bisect_right
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.islands import IslandConfig, NOC_LADDER, TILE_LADDER
from repro.core.noc import pos_index
from repro.core.perfmodel import (AccelWorkload, NOC_POWER_SHARE,
                                  SoCPerfModel, chip_power,
                                  chip_power_coeffs,
                                  _memory_traffic_math_per_accel,
                                  _throughput_math)
from repro.core.replication import (replication_area_model,
                                    replication_throughput_model)
from repro.core.tiles import TilePlan
from repro.core.voltage import TechModel, tech_axis_coeffs


@dataclass(frozen=True)
class DesignPoint:
    replication: Dict[str, int]
    rates: Dict[str, float]
    placement: Dict[str, Tuple[int, int]]
    throughput: float
    area: float                    # normalized resource cost
    energy_per_unit: float
    tech: Optional[Tuple[int, str]] = None   # (node, variant) when swept

    def key(self):
        return (tuple(sorted(self.replication.items())),
                tuple(sorted(self.rates.items())),
                tuple(sorted(self.placement.items())),
                self.tech)


# ---------------------------------------------------------------------------
# Pareto fronts
# ---------------------------------------------------------------------------


def pareto_front_indices(throughput, area, energy) -> np.ndarray:
    """Indices of the 3-objective Pareto front in O(N log N).

    Maximize ``throughput``; minimize ``area`` and ``energy``.  Points are
    processed in descending-throughput groups; a (area, energy) staircase
    of the already-accepted, strictly-faster points answers "is this point
    dominated?" in O(log F).  Semantics match the O(N^2) brute force: q
    dominates p iff q is >=/<=/<= on all three objectives and strictly
    better on at least one (exact duplicates do not dominate each other).
    Returns indices in ascending input order.
    """
    thr = np.asarray(throughput, dtype=np.float64)
    area = np.asarray(area, dtype=np.float64)
    energy = np.asarray(energy, dtype=np.float64)
    n = thr.shape[0]
    if n == 0:
        return np.empty(0, dtype=np.int64)
    order = np.lexsort((energy, area, -thr))
    # python lists: ~3x faster to index in the scan than numpy scalars
    thr_l = thr[order].tolist()
    area_l = area[order].tolist()
    energy_l = energy[order].tolist()
    order_l = order.tolist()

    keep: List[int] = []
    stair_a: List[float] = []       # staircase areas, ascending
    stair_e: List[float] = []       # matching energies, strictly descending
    INF = float("inf")
    i = 0
    while i < n:
        j = i
        t = thr_l[i]
        while j < n and thr_l[j] == t:
            j += 1
        # 1) cull against strictly-faster accepted points
        survivors = []
        for p in range(i, j):
            a, e = area_l[p], energy_l[p]
            s = bisect_right(stair_a, a)
            if s > 0 and stair_e[s - 1] <= e:
                continue                      # dominated by a faster point
            survivors.append(p)
        # 2) within-group dominance (equal throughput; needs strictness).
        # survivors are sorted by (area, energy) thanks to the lexsort.
        best_e_smaller_area = INF             # min energy over area < cur
        cur_area, cur_min_e = None, INF       # min energy within area == cur
        kept_group: List[Tuple[float, float]] = []
        for p in survivors:
            a, e = area_l[p], energy_l[p]
            if a != cur_area:
                best_e_smaller_area = min(best_e_smaller_area, cur_min_e)
                cur_area, cur_min_e = a, INF
            if not (best_e_smaller_area <= e or cur_min_e < e):
                keep.append(order_l[p])
                kept_group.append((a, e))
            cur_min_e = min(cur_min_e, e)
        # 3) fold the group's minimal (area, energy) pairs into the staircase
        for a, e in kept_group:
            s = bisect_right(stair_a, a)
            if s > 0 and stair_e[s - 1] <= e:
                continue                      # already covered
            stair_a.insert(s, a)
            stair_e.insert(s, e)
            k = s + 1
            while k < len(stair_a) and stair_e[k] >= e:
                k += 1
            del stair_a[s + 1:k]
            del stair_e[s + 1:k]
        i = j
    keep.sort()
    return np.asarray(keep, dtype=np.int64)


def pareto_front_bruteforce(points: Sequence[DesignPoint]) -> List[DesignPoint]:
    """O(N^2) reference implementation (kept for verification/tests)."""
    front: List[DesignPoint] = []
    for p in points:
        dominated = False
        for q in points:
            if q is p:
                continue
            if (q.throughput >= p.throughput and q.area <= p.area
                    and q.energy_per_unit <= p.energy_per_unit
                    and (q.throughput > p.throughput or q.area < p.area
                         or q.energy_per_unit < p.energy_per_unit)):
                dominated = True
                break
        if not dominated:
            front.append(p)
    return front


def pareto_front(points: Sequence[DesignPoint]) -> List[DesignPoint]:
    """Maximize throughput, minimize area & energy — O(N log N)."""
    pts = list(points)
    idx = pareto_front_indices(
        np.asarray([p.throughput for p in pts]),
        np.asarray([p.area for p in pts]),
        np.asarray([p.energy_per_unit for p in pts]))
    return [pts[i] for i in idx]


# ---------------------------------------------------------------------------
# Batched grid sweep
# ---------------------------------------------------------------------------


class _SweepIndexing:
    """Index machinery shared by the one-shot and chunked sweep results.

    Both carry the ordered ``axes`` (name, values) and the grid ``shape``;
    flat point indices are C-ordered over ``shape``, so any flat index —
    whether its objectives are stored densely (:class:`SweepResult`) or
    only for tracked survivors (:class:`ChunkedSweepResult`) — maps back
    to concrete axis values, per-island rate vectors and
    :class:`DesignPoint` objects the same way.  Subclasses provide
    ``axes``/``shape``/``workloads``/``n_tg`` plus
    :meth:`objective_values`.
    """

    @property
    def independent_islands(self) -> bool:
        """True when each accelerator island swept its own rate axis."""
        return all(name != "f_acc" for name, _ in self.axes)

    def axis_values(self, i: int) -> Dict[str, object]:
        """Swept axis values of flat point ``i`` as {axis_name: value}."""
        coords = np.unravel_index(i, self.shape)
        return {name: values[c]
                for (name, values), c in zip(self.axes, coords)}

    def _accel_rate(self, av: Dict[str, object], wl_name: str) -> float:
        key = f"f_acc:{wl_name}"
        return float(av[key] if key in av else av["f_acc"])

    def island_rates(self, i: int) -> Dict[str, float]:
        """Per-island rate vector of flat point ``i``: one entry per
        accelerator island (keyed by workload/tile name, the island naming
        ``repro.sim.SimPlatform.build`` uses) plus the shared ``noc_mem``
        island.  In shared mode every accelerator entry is the one swept
        ``f_acc``; the TG rate is an axis value (``axis_values``), not an
        island."""
        av = self.axis_values(i)
        out = {wl.name: self._accel_rate(av, wl.name)
               for wl in self.workloads}
        out["noc_mem"] = float(av["f_noc"])
        return out

    def design_point(self, i: int) -> DesignPoint:
        """Materialize one flat index as a :class:`DesignPoint`."""
        av = self.axis_values(i)
        replication = {wl.name: int(av[f"K:{wl.name}"])
                       for wl in self.workloads}
        placement = {wl.name: tuple(av[f"pos:{wl.name}"])
                     for wl in self.workloads}
        if self.independent_islands:
            rates = {wl.name: self._accel_rate(av, wl.name)
                     for wl in self.workloads}
        else:
            rates = {"acc": float(av["f_acc"])}
        rates["noc_mem"] = float(av["f_noc"])
        rates["tg"] = float(av["f_tg"])
        thr, area, energy = self._point_objectives(i)
        tech = av.get("tech")
        return DesignPoint(
            replication=replication, rates=rates, placement=placement,
            throughput=thr, area=area, energy_per_unit=energy,
            tech=None if tech is None else (int(tech[0]), str(tech[1])))

    def _point_objectives(self, i: int) -> Tuple[float, float, float]:
        return tuple(
            float(self.objective_values(name, np.asarray([i]))[0])
            for name in ("throughput", "area", "energy_per_unit"))

    def design_points(self, indices: Iterable[int]) -> List[DesignPoint]:
        return [self.design_point(int(i)) for i in indices]

    def design_arrays(self, indices) -> Dict[str, np.ndarray]:
        """Vectorized design decode for B flat indices — the batched-sim
        bridge (``repro.sim.BatchSimPlatform.from_design_points``).

        Returns ``k`` (B, A) float64 replication, ``pos`` (B, A, 2) int64
        grid coordinates, ``rates`` (B, A+1) float64 per-island rates in
        ``[*workload names, "noc_mem"]`` order, and ``f_tg`` (B,) float64
        — exactly the floats :meth:`design_point` would produce, without
        materializing B DesignPoints.
        """
        idx = np.asarray(indices, dtype=np.int64)
        coords = dict(zip((n for n, _ in self.axes),
                          np.unravel_index(idx, self.shape)))
        vals = {n: np.asarray(v) for n, v in self.axes}

        def axis(name):
            return vals[name][coords[name]]

        k = np.stack([axis(f"K:{wl.name}").astype(np.float64)
                      for wl in self.workloads], axis=-1)
        pos = np.stack([axis(f"pos:{wl.name}") for wl in self.workloads],
                       axis=-2).astype(np.int64)
        fa_cols = [axis(f"f_acc:{wl.name}"
                        if self.independent_islands else "f_acc")
                   for wl in self.workloads]
        rates = np.stack(fa_cols + [axis("f_noc")], axis=-1).astype(
            np.float64)
        return {"k": k, "pos": pos, "rates": rates,
                "f_tg": axis("f_tg").astype(np.float64)}


# Objectives tracked by the chunked streaming sweep: name -> maximize?
_TRACKED_OBJECTIVES = (("throughput", True), ("area", False),
                       ("energy_per_unit", False), ("mem_traffic", False))


def _topk_select(key: np.ndarray, indices: np.ndarray, k: int) -> np.ndarray:
    """Positions of the k smallest ``key`` entries, ordered — and, at the
    k-th-value boundary, *selected* — by (key, global index).

    argpartition alone picks arbitrarily among boundary ties, which would
    make one-shot and chunked sweeps disagree on tie-heavy objectives
    (area has a handful of distinct values); widening the partition to
    every entry tied with the k-th value and resolving by flat index makes
    the selection deterministic and chunking-invariant."""
    n = key.shape[0]
    k = min(k, n)
    if k == 0:
        return np.empty(0, dtype=np.int64)
    if k < n:
        part = np.argpartition(key, k - 1)[:k]
        cand = np.nonzero(key <= key[part].max())[0]
    else:
        cand = np.arange(n)
    order = np.lexsort((indices[cand], key[cand]))[:k]
    return cand[order]


@dataclass(eq=False)
class SweepResult(_SweepIndexing):
    """Objective arrays for a full cross-product sweep, plus lazy
    :class:`DesignPoint` materialization.

    ``axes`` is the ordered list of (name, values) swept dimensions; flat
    arrays are C-ordered over ``shape``, so axis values for point ``i`` are
    recovered with ``np.unravel_index`` — no per-point objects exist until
    :meth:`design_point` is called for a survivor.
    """
    axes: Tuple[Tuple[str, Tuple], ...]
    shape: Tuple[int, ...]
    workloads: Tuple[AccelWorkload, ...]
    n_tg: int
    throughput: np.ndarray              # (N,) float64, total across accels
    area: np.ndarray                    # (N,) float64
    energy_per_unit: np.ndarray         # (N,) float64
    valid: np.ndarray                   # (N,) bool (placement collisions out)
    mem_traffic: Optional[np.ndarray] = None   # (N,) float64, Fig.-4 model
    elapsed_s: float = 0.0

    def __len__(self) -> int:
        return int(self.throughput.shape[0])

    @property
    def n_valid(self) -> int:
        return int(self.valid.sum())

    @property
    def points_per_second(self) -> float:
        return len(self) / self.elapsed_s if self.elapsed_s > 0 else float("inf")

    def objective_values(self, objective: str, indices) -> np.ndarray:
        """Objective array values at flat ``indices`` (dense lookup)."""
        return getattr(self, objective)[np.asarray(indices, dtype=np.int64)]

    def pareto_indices(self) -> np.ndarray:
        """Flat indices of the (valid-only) Pareto front, O(N log N)."""
        flat = np.nonzero(self.valid)[0]
        sub = pareto_front_indices(self.throughput[flat], self.area[flat],
                                   self.energy_per_unit[flat])
        return flat[sub]

    def topk_indices(self, k: int, objective: str = "throughput",
                     maximize: Optional[bool] = None) -> np.ndarray:
        """Flat indices of the k best valid points on one objective,
        best-first, via argpartition (no full sort, no DesignPoints).
        Exact ties order by ascending flat index (the same deterministic
        tie-break the chunked sweep's running top-k merge uses)."""
        vals = getattr(self, objective)
        if maximize is None:
            maximize = objective == "throughput"
        flat = np.nonzero(self.valid)[0]
        v = vals[flat]
        key = -v if maximize else v
        return flat[_topk_select(key, flat, k)]


@dataclass(eq=False)
class ChunkedSweepResult(_SweepIndexing):
    """Survivors of a chunked/streaming :func:`grid_sweep`.

    The full grid (``len(self)`` points, possibly >1e8) was evaluated in
    fixed-size axis blocks and never materialized whole; only the running
    Pareto front and the per-objective top-``topk_track`` survivors are
    retained, with **globally addressable** flat indices — the same
    C-order over ``shape`` a one-shot :class:`SweepResult` uses, so
    :meth:`axis_values` / :meth:`design_point` / downstream consumers
    (``closed_loop_score``, ``BatchSimPlatform.from_design_points``) work
    unchanged.  Objective *values* are only retained for tracked
    survivors: :meth:`objective_values` raises ``KeyError`` for other
    indices, and :meth:`design_point` on an untracked index still decodes
    replication/placement/rates exactly but carries NaN objectives.
    """
    axes: Tuple[Tuple[str, Tuple], ...]
    shape: Tuple[int, ...]
    workloads: Tuple[AccelWorkload, ...]
    n_tg: int
    n_points: int
    n_valid: int
    cand_indices: np.ndarray            # (M,) int64, sorted ascending
    cand_values: Dict[str, np.ndarray]  # objective -> (M,) float64
    pareto: np.ndarray                  # (F,) int64 global, ascending
    topk: Dict[str, np.ndarray]         # objective -> best-first global idx
    topk_track: int
    chunk_points: int
    n_chunks: int
    peak_chunk_bytes: int
    elapsed_s: float = 0.0

    def __len__(self) -> int:
        return self.n_points

    @property
    def points_per_second(self) -> float:
        return len(self) / self.elapsed_s if self.elapsed_s > 0 else float("inf")

    def objective_values(self, objective: str, indices) -> np.ndarray:
        """Objective values at flat ``indices`` — tracked survivors only."""
        idx = np.atleast_1d(np.asarray(indices, dtype=np.int64))
        pos = np.searchsorted(self.cand_indices, idx)
        ok = (pos < self.cand_indices.shape[0]) \
            & (self.cand_indices[np.minimum(
                pos, self.cand_indices.shape[0] - 1)] == idx)
        if not ok.all():
            raise KeyError(
                f"flat indices {idx[~ok][:5].tolist()} are not tracked "
                "survivors of this chunked sweep (only Pareto/top-k points "
                "retain objective values)")
        return self.cand_values[objective][pos]

    def _point_objectives(self, i: int) -> Tuple[float, float, float]:
        """Tracked survivors report their stored objectives; any other
        (still decodable) index degrades to NaN objectives rather than
        refusing to materialize."""
        try:
            return _SweepIndexing._point_objectives(self, i)
        except KeyError:
            return (float("nan"),) * 3

    def pareto_indices(self) -> np.ndarray:
        """Global flat indices of the full-grid Pareto front (the running
        block merge is exact: front(union) == front(union of block
        fronts)), ascending — identical to the one-shot sweep's."""
        return self.pareto

    def topk_indices(self, k: int, objective: str = "throughput",
                     maximize: Optional[bool] = None) -> np.ndarray:
        """Best-first global indices on one objective, ``k <= topk_track``.
        Identical to the one-shot sweep's (ties broken by flat index)."""
        default = dict(_TRACKED_OBJECTIVES)
        if maximize is None:
            maximize = objective == "throughput"
        if objective not in default or maximize != default[objective]:
            raise KeyError(
                f"chunked sweeps track top-k only for {sorted(default)} in "
                "their default directions")
        if k > self.topk_track:
            raise ValueError(
                f"k={k} exceeds topk_track={self.topk_track} retained by "
                "this chunked sweep; re-run grid_sweep with a larger "
                "topk_track")
        return self.topk[objective][:k]


def _axis(values, dim: int, ndim: int) -> np.ndarray:
    """Reshape a 1-D axis to broadcast at dimension ``dim`` of ``ndim``."""
    a = np.asarray(values)
    shape = [1] * ndim
    shape[dim] = a.shape[0]
    return a.reshape(shape)


@dataclass(frozen=True)
class _AxisLayout:
    """Dimension layout of one sweep: per-accel K axes, ``f_noc``, the
    shared or per-accel ``f_acc`` axes, ``f_tg``, per-accel pos axes,
    plus an optional trailing combined ``tech`` axis (node, variant)."""
    A: int
    independent: bool
    tech: bool = False

    @property
    def R(self) -> int:
        return self.A if self.independent else 1

    @property
    def ndim(self) -> int:
        return 2 * self.A + self.R + 2 + (1 if self.tech else 0)

    @property
    def tdim(self) -> int:
        assert self.tech, "no tech axis in this sweep"
        return 2 * self.A + self.R + 2

    def k(self, a: int) -> int:
        return a

    @property
    def fnoc(self) -> int:
        return self.A

    def fa(self, a: int) -> int:
        return self.A + 1 + (a if self.independent else 0)

    @property
    def ftg(self) -> int:
        return self.A + 1 + self.R

    def pos(self, a: int) -> int:
        return self.A + 2 + self.R + a


def _eval_grid(model: SoCPerfModel, workloads, n_tg: int,
               lay: _AxisLayout, vals: Dict[str, object], get,
               shape: Tuple[int, ...]) -> Dict[str, np.ndarray]:
    """Evaluate every objective over one (sub-)grid.

    ``get(dim, values)`` returns the broadcastable array of an axis for
    this block; the arithmetic is purely elementwise + fixed-order accel
    loops, so any blocking of the grid produces bit-identical floats —
    the chunked sweep's correctness contract.  The energy model routes the
    shared-rate case through the *same* per-accel op sequence as the
    independent case (sum over accel islands in order, then /A), which is
    what makes all-islands-equal independent points reproduce the shared
    sweep bit for bit.
    """
    A = lay.A
    k_ax = [get(lay.k(a), vals["k"]) for a in range(A)]
    fn_ax = get(lay.fnoc, vals["noc"])
    fa_ax = [get(lay.fa(a), vals["acc"][a]) for a in range(A)]
    ft_ax = get(lay.ftg, vals["tg"])
    pos_ax = [get(lay.pos(a), vals["pos"]) for a in range(A)]

    total_thr = np.zeros(shape, dtype=np.float64)
    for a, wl in enumerate(workloads):
        thr = model.accel_throughput_batch(
            base_mbps=wl.base_mbps, wire_share=wl.wire_share, k=k_ax[a],
            f_acc=fa_ax[a], f_noc=fn_ax, f_tg=ft_ax, n_tg=n_tg,
            pos_idx=pos_ax[a])
        total_thr = total_thr + np.broadcast_to(thr, shape)

    area = np.zeros(shape, dtype=np.float64)
    for a in range(A):
        area = area + get(lay.k(a), vals["area"])

    # mean accelerator-island power (summed in accel order, then /A) +
    # the NoC share — one op sequence for both island_rates modes
    if lay.tech:
        # physical V^2 f model: per-tech-axis (p_scale, v0, v1) coefficients
        ps = get(lay.tdim, vals["tech_ps"])
        v0 = get(lay.tdim, vals["tech_v0"])
        v1 = get(lay.tdim, vals["tech_v1"])
        pw = chip_power_coeffs(fa_ax[0], 1.0, v0, v1, ps)
        for f in fa_ax[1:]:
            pw = pw + chip_power_coeffs(f, 1.0, v0, v1, ps)
        power = pw / float(A) \
            + NOC_POWER_SHARE * chip_power_coeffs(fn_ax, 1.0, v0, v1, ps)
    else:
        pw = chip_power(fa_ax[0], busy=1.0)
        for f in fa_ax[1:]:
            pw = pw + chip_power(f, busy=1.0)
        power = pw / float(A) + NOC_POWER_SHARE * chip_power(fn_ax, busy=1.0)
    energy = np.broadcast_to(power, shape) / np.maximum(total_thr, 1e-9)

    # Fig.-4 memory-pressure objective: offered MEM traffic at each rate
    # point (placement-independent, so it broadcasts over the K/pos axes)
    mem_traffic = np.broadcast_to(
        model.memory_traffic_batch(f_acc_per_accel=fa_ax, f_noc=fn_ax,
                                   f_tg=ft_ax, n_tg=n_tg), shape)

    valid = np.ones(shape, dtype=bool)
    for a in range(A):
        for b in range(a + 1, A):
            valid &= pos_ax[a] != pos_ax[b]

    return {"throughput": total_thr,
            "area": np.ascontiguousarray(np.broadcast_to(area, shape)),
            "energy_per_unit": energy,
            "mem_traffic": np.ascontiguousarray(mem_traffic),
            "valid": valid}


def _span(name: str):
    """A span of the program's recorder, :func:`repro.sim.observe.profiled`
    (imported lazily: the core DSE layer stays importable without
    ``repro.sim``)."""
    from repro.sim.observe import profiled
    return profiled(name)


def _spanned(name: str):
    """Decorator recording every call of the function as span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with _span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


# value tables are rows of this many entries (or of the next power of two
# that holds the longest): every space whose axes have at most this many
# values runs the same executable
_TABLE_WIDTH = 32


def _device_tables(model: SoCPerfModel, lay: _AxisLayout,
                   vals: Dict[str, object]) -> np.ndarray:
    """The value tables the flat-point evaluator looks axis values up in:
    one float32 row each, zero-padded to :data:`_TABLE_WIDTH` entries (or
    the next power of two that holds the longest).  Rows, in order: the
    K ladder, the NoC ladder, one rate ladder per rate axis (``lay.R``),
    the TG ladder, the hop count of each candidate position, then the
    tech axis's ``p_scale``, ``v0`` and ``v1``.  Each is cast from the
    float64 the host path holds, so every value is the float32 it was
    on the host."""
    rows = [vals["k"], vals["noc"], *vals["acc"][:lay.R], vals["tg"],
            model.hop_counts(pos_idx=vals["pos"])]
    if lay.tech:
        rows += [vals["tech_ps"], vals["tech_v0"], vals["tech_v1"]]
    longest = max(len(r) for r in rows)
    tables = np.zeros((len(rows), max(_TABLE_WIDTH,
                                      1 << (longest - 1).bit_length())),
                      dtype=np.float32)
    for t, r in enumerate(rows):
        tables[t, :len(r)] = np.asarray(r, dtype=np.float64)
    return tables


def _divmod(q, n):
    """``(q // n, q % n)`` of int32 jax arrays, ``0 <= q < 2**30`` and
    ``n >= 1``, from float32 quotients: an estimate and a second pass on
    its remainder leave the quotient at most one off, and a compare puts
    it right.  (For a TPU v5e, one int32 division by a traced divisor
    takes the compiler about a minute.)"""
    import jax.numpy as jnp
    i32, f32 = jnp.int32, jnp.float32
    nf = n.astype(f32)
    d = jnp.floor(q.astype(f32) / nf).astype(i32)
    d = d + jnp.floor((q - d * n).astype(f32) / nf).astype(i32)
    r = q - d * n
    fix = (r >= n).astype(i32) - (r < 0).astype(i32)
    return d + fix, r - fix * n


def _decode_digits(i, start, sizes):
    """Axis coordinates of the flat points ``start + i`` (C order), in
    int32 jax math: ``i`` (P,) holds offsets into a chunk, below 2**30,
    ``start`` (ndim,) the coordinates of the chunk's first point and
    ``sizes`` (ndim,) the axis sizes.  The digits of ``i`` are added to
    ``start``'s with carries, last axis first, so no int32 ever holds a
    global flat index (spaces can exceed 2**31 points).  Returns ndim
    (P,) arrays; lanes past the space's last point wrap around."""
    import jax.numpy as jnp
    digits = [None] * start.shape[0]
    q, carry = i, jnp.zeros_like(i)
    for d in reversed(range(start.shape[0])):
        n = jnp.broadcast_to(sizes[d], i.shape)
        q, r = _divmod(q, n)
        s = r + start[d] + carry
        carry = (s >= n).astype(i.dtype)
        digits[d] = s - carry * n
    return digits


def _objectives(A: int, n_tg: int, base_wire, own_demand: float,
                tg_demand: float, link_bw: float, hop_latency_share: float,
                ref_hops: float, mem_service: float, tg_demand_fig4: float,
                kA, faA, hopA, f_noc, f_tg, tech=None):
    """(throughput, energy per unit, memory traffic) of flat points from
    their axis values, in jax: ``kA``, ``faA``, ``hopA`` hold one (P,)
    row per accelerator, ``tech`` the (p_scale, v0, v1) arrays of the
    physical power model or None for the linear voltage proxy.

    The math is the same fixed-order accel loop as :func:`_eval_grid`
    (``_throughput_math`` / ``chip_power`` / the per-accel Fig.-4 memory
    model)."""
    import jax.numpy as jnp

    thr = jnp.zeros_like(f_noc)
    for a, (base, wire) in enumerate(base_wire):
        thr = thr + _throughput_math(
            jnp, base, wire, kA[a], faA[a], f_noc, f_tg, n_tg, hopA[a],
            own_demand=own_demand, tg_demand=tg_demand, link_bw=link_bw,
            hop_latency_share=hop_latency_share, ref_hops=ref_hops)
    mem = _memory_traffic_math_per_accel(
        jnp, [faA[a] for a in range(A)], f_noc, f_tg, n_tg,
        mem_service=mem_service, tg_demand_fig4=tg_demand_fig4)
    if tech is not None:
        ps, v0, v1 = tech
        pw = chip_power_coeffs(faA[0], 1.0, v0, v1, ps)
        for a in range(1, A):
            pw = pw + chip_power_coeffs(faA[a], 1.0, v0, v1, ps)
        power = pw / float(A) \
            + NOC_POWER_SHARE * chip_power_coeffs(f_noc, 1.0, v0, v1, ps)
    else:
        pw = chip_power(faA[0], busy=1.0)
        for a in range(1, A):
            pw = pw + chip_power(faA[a], busy=1.0)
        power = pw / float(A) + NOC_POWER_SHARE * chip_power(f_noc,
                                                            busy=1.0)
    energy = power / jnp.maximum(thr, 1e-9)
    return thr, energy, mem


def _model_scalars(model: SoCPerfModel, workloads, n_tg: int) -> tuple:
    """The scalars :func:`_objectives` takes before its arrays (and the
    flat-point evaluator's cache key after the device count)."""
    return (len(workloads), int(n_tg),
            tuple((float(wl.base_mbps), float(wl.wire_share))
                  for wl in workloads),
            float(model.own_demand), float(model.tg_demand),
            float(model.noc.link_bw), float(model.hop_latency_share),
            float(model._ref_hops()), float(model.mem_service),
            float(model.tg_demand_fig4))


# bounded: one executable per (device count, model constants, layout)
# combination actually swept in this process — keyed on scalars only,
# never arrays; jit adds one per static point count
@lru_cache(maxsize=8)
def _flat_point_evaluator(n_devices: int, A: int, n_tg: int,
                          base_wire: Tuple[Tuple[float, float], ...],
                          own_demand: float, tg_demand: float,
                          link_bw: float, hop_latency_share: float,
                          ref_hops: float, mem_service: float,
                          tg_demand_fig4: float, tech: bool = False,
                          independent: bool = False):
    """jit-compiled (and, for ``n_devices > 1``, ``shard_map``-sharded)
    evaluator of the three float objectives of P consecutive flat points
    of a sweep, which it decodes itself.

    Called as ``fn(P, start, sizes, tables)``: ``P`` the (static) point
    count, a multiple of ``n_devices``; ``start`` the int32 coordinates
    of the first point; ``sizes`` the int32 axis sizes of the layout
    ``_AxisLayout(A, independent, tech)``; ``tables`` from
    :func:`_device_tables`.  Each point's coordinates come from an iota
    by :func:`_decode_digits`, its axis values from its table row by a
    chain of selects (exact; on a TPU v5e some 80 times faster than a
    gather from the same row), and :func:`_objectives` does the math.  Returns ``(thr,
    energy, mem)``, each (P,) float32.  The executable depends on P, the
    layout and the tables' width, never on how many values an axis has,
    so a cut space compiles what the whole space runs.

    Sharding only splits an elementwise computation (each shard adds its
    own offset), so every device count produces identical floats —
    tested 1-vs-N in ``tests/test_shard_pallas.py``.  Runs in float32,
    so results deviate ~1e-6 relative from the numpy f64 path, which
    stays the ground truth for ``devices=None``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro import shard as shard_mod
    from jax.sharding import PartitionSpec

    lay = _AxisLayout(A=A, independent=independent, tech=tech)
    R = lay.R
    scalars = (A, n_tg, base_wire, own_demand, tg_demand, link_bw,
               hop_latency_share, ref_hops, mem_service, tg_demand_fig4)

    def points(n, first, start, sizes, tables):
        def lookup(_):
            d = _decode_digits(lax.iota(jnp.int32, n) + first, start, sizes)

            def value(table, dim):
                v = jnp.broadcast_to(tables[table, 0], (n,))
                for j in range(1, tables.shape[1]):
                    v = jnp.where(d[dim] == j, tables[table, j], v)
                return v
            return (jnp.stack([value(0, lay.k(a)) for a in range(A)]),
                    jnp.stack([value(2 + (a if independent else 0),
                                     lay.fa(a)) for a in range(A)]),
                    jnp.stack([value(3 + R, lay.pos(a)) for a in range(A)]),
                    value(1, lay.fnoc), value(2 + R, lay.ftg),
                    *(value(4 + R + j, lay.tdim)
                      for j in range(3 if tech else 0)))

        def zeros(_):
            return tuple(jnp.zeros(s, jnp.float32) for s in
                         [(A, n)] * 3 + [(n,)] * (5 if tech else 2))

        # the axis sizes are positive, so the decode always runs; XLA
        # fuses nothing across a conditional, so the math below compiles
        # as it did on these arrays uploaded from the host, down to where
        # a backend contracts a product into a sum: the same floats
        kA, faA, hopA, f_noc, f_tg, *coeffs = lax.cond(
            sizes[0] > 0, lookup, zeros, None)
        return _objectives(*scalars, kA, faA, hopA, f_noc, f_tg,
                           coeffs or None)

    if n_devices <= 1:
        def fn(P, start, sizes, tables):
            return points(P, 0, start, sizes, tables)
        return jax.jit(fn, static_argnums=0)

    mesh = shard_mod.device_mesh(n_devices, "points")
    rep, s1 = PartitionSpec(), PartitionSpec("points")

    def fn(P, start, sizes, tables):
        n = P // n_devices

        def shard(*args):
            return points(n, lax.axis_index("points") * n, *args)
        return jax.shard_map(shard, mesh=mesh, in_specs=(rep,) * 3,
                             out_specs=(s1,) * 3, check_vma=False)(
            start, sizes, tables)
    return jax.jit(fn, static_argnums=0)


def _eval_flat_points(model: SoCPerfModel, workloads, n_tg: int,
                      lay: _AxisLayout, vals: Dict[str, object],
                      shape: Tuple[int, ...], lo: int, hi: int, get,
                      blk_shape: Tuple[int, ...],
                      n_devices: int) -> Dict[str, np.ndarray]:
    """Evaluate global flat points ``[lo, hi)``, the block ``blk_shape``
    whose axis arrays ``get(dim, values)`` gives, as flat (P,) arrays.

    The host decodes only ``lo`` into coordinates and broadcasts the
    area sum and the placement-validity mask (float64 and bool, as
    :func:`_eval_grid` does); :func:`_flat_point_evaluator` decodes every
    point on the device and returns the float objectives in float32,
    which callers cast to float64 (the chunked sweep after selecting the
    valid rows).  The point axis is padded to a device multiple (padded
    lanes are sliced off).
    """
    from repro import shard as shard_mod
    from repro.sim.observe import get_profiler

    A = lay.A
    P = hi - lo
    n = shard_mod.shard_len(P, n_devices)
    if n >= 2 ** 30:
        raise ValueError(f"{P} points in one evaluator call; pass a "
                         "chunk_points below 2**30")
    with _span("sweep_decode"):
        ones = (1,) * len(blk_shape)
        area = np.zeros(ones, dtype=np.float64)
        for a in range(A):
            area = area + get(lay.k(a), vals["area"])
        pos_ax = [get(lay.pos(a), vals["pos"]) for a in range(A)]
        valid = np.ones(ones, dtype=bool)
        for a in range(A):
            for b in range(a + 1, A):
                valid = valid & (pos_ax[a] != pos_ax[b])
        area = np.broadcast_to(area, blk_shape).ravel()
        valid = np.broadcast_to(valid, blk_shape).ravel()
        start = np.asarray(np.unravel_index(lo, shape), dtype=np.int32)
        tables = _device_tables(model, lay, vals)

    # the evaluator call (transfer of the start and the tables, device
    # decode and math) and the fetch
    with _span("sweep_device_call"):
        evaluator = _flat_point_evaluator(
            int(n_devices), *_model_scalars(model, workloads, n_tg),
            tech=lay.tech, independent=lay.independent)
        thr, energy, mem = evaluator(n, start,
                                     np.asarray(shape, dtype=np.int32),
                                     tables)
        out = {"throughput": np.asarray(thr)[:P], "area": area,
               "energy_per_unit": np.asarray(energy)[:P],
               "mem_traffic": np.asarray(mem)[:P], "valid": valid}
    get_profiler().count("sweep_points_decoded_on_device", P)
    return out


def _prepare_axes(model, workloads, ks, acc_rates, noc_rates, tg_rates,
                  positions, island_rates, tech_node=None,
                  tech_variant=None):
    """Axis bookkeeping shared by the one-shot and chunked paths."""
    assert island_rates in ("shared", "independent"), island_rates
    independent = island_rates == "independent"

    # tech_node / tech_variant combine into ONE trailing "tech" axis whose
    # values are (node, variant) pairs — the cross product of both inputs —
    # so the 1-D axis broadcast/chunk machinery applies unchanged
    techs: Tuple[Tuple[int, str], ...] = ()
    if tech_node is not None or tech_variant is not None:
        nodes = 45 if tech_node is None else tech_node
        if isinstance(nodes, (int, np.integer)):
            nodes = (nodes,)
        variants = "itrs" if tech_variant is None else tech_variant
        if isinstance(variants, str):
            variants = (variants,)
        techs = tuple((int(n), str(v)) for n in nodes for v in variants)
    if positions is None:
        positions = [(r, c) for r in range(model.noc.rows)
                     for c in range(model.noc.cols)
                     if (r, c) != model.mem_pos]
    positions = [tuple(p) for p in positions]
    pos_idx = np.asarray([pos_index(model.noc, p) for p in positions])

    if isinstance(acc_rates, dict):
        assert independent, "per-accel acc_rates ladders require " \
            "island_rates='independent'"
        acc_by_wl = [tuple(float(f) for f in acc_rates[wl.name])
                     for wl in workloads]
    else:
        acc_by_wl = [tuple(float(f) for f in acc_rates)] * len(workloads)

    A = len(workloads)
    lay = _AxisLayout(A=A, independent=independent, tech=bool(techs))
    axes: List[Tuple[str, Tuple]] = []
    for wl in workloads:
        axes.append((f"K:{wl.name}", tuple(int(k) for k in ks)))
    axes.append(("f_noc", tuple(float(f) for f in noc_rates)))
    if independent:
        for a, wl in enumerate(workloads):
            axes.append((f"f_acc:{wl.name}", acc_by_wl[a]))
    else:
        axes.append(("f_acc", acc_by_wl[0]))
    axes.append(("f_tg", tuple(float(f) for f in tg_rates)))
    for wl in workloads:
        axes.append((f"pos:{wl.name}", tuple(positions)))
    if techs:
        axes.append(("tech", techs))

    area_by_k = {int(k): replication_area_model(
        weight_bytes=1.0, act_bytes=0.5, k=int(k))["total_bytes_per_dev"]
        for k in ks}
    vals = {
        "k": np.asarray([float(k) for k in ks]),
        "area": np.asarray([area_by_k[int(k)] for k in ks]),
        "noc": np.asarray([float(f) for f in noc_rates]),
        "tg": np.asarray([float(f) for f in tg_rates]),
        "acc": [np.asarray(r) for r in acc_by_wl],
        "pos": pos_idx,
    }
    if techs:
        vals.update(tech_axis_coeffs(techs))
    return lay, tuple(axes), vals


def _front_prefilter(thr: np.ndarray, area: np.ndarray, energy: np.ndarray,
                     max_classes: int = 1024) -> np.ndarray:
    """Positions of a cheap *superset* of the 3-objective Pareto front.

    Per distinct-area class (area takes one value per K combination — a
    handful), the 2-objective (max throughput, min energy) staircase via
    one lexsort + cumulative min; any point dominated there is dominated
    in 3D by the same point (equal area), so the exact — but per-point
    Python — :func:`pareto_front_indices` scan afterwards only sees the
    small candidate set.  This is what keeps the chunked sweep's per-block
    front extraction vectorized at millions of points per block.  Falls
    back to the identity when area is effectively continuous."""
    uniq = np.unique(area)
    if uniq.shape[0] > max_classes:
        return np.arange(thr.shape[0])
    keep: List[np.ndarray] = []
    for av in uniq:
        sel = np.nonzero(area == av)[0]
        o = sel[np.lexsort((energy[sel], -thr[sel]))]
        cm = np.minimum.accumulate(energy[o])
        keep.append(o[energy[o] <= cm])     # over-keeps ties; exact scan next
    return np.concatenate(keep)


def _merge_front(cand: Dict[str, np.ndarray],
                 rows: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Fold one block's Pareto survivors into the running front."""
    merged = {k: np.concatenate([cand[k], rows[k]]) for k in cand}
    keep = pareto_front_indices(merged["throughput"], merged["area"],
                                merged["energy_per_unit"])
    return {k: v[keep] for k, v in merged.items()}


@_spanned("grid_sweep")
def grid_sweep(model: SoCPerfModel,
               workloads,
               *,
               ks: Sequence[int] = (1, 2, 4),
               acc_rates=(0.2, 0.6, 1.0),
               noc_rates: Sequence[float] = (0.1, 0.5, 1.0),
               tg_rates: Sequence[float] = (1.0,),
               positions: Optional[Sequence[Tuple[int, int]]] = None,
               n_tg: int = 0,
               island_rates: str = "shared",
               chunk_points: Optional[int] = None,
               topk_track: int = 64,
               devices=None,
               tech_node=None,
               tech_variant=None):
    """Batched cross-product sweep over the paper's design axes.

    ``workloads`` is one :class:`AccelWorkload` or a sequence for a *joint*
    multi-accelerator sweep (each accelerator gets its own K axis and its
    own placement axis).  The swept dimensions, in axis order, are::

        island_rates="shared":       K:<wl> | f_noc | f_acc        | f_tg | pos:<wl>
        island_rates="independent":  K:<wl> | f_noc | f_acc:<wl>.. | f_tg | pos:<wl>

    **Per-island rates** (the paper's C2): with
    ``island_rates="independent"`` every accelerator island sweeps its own
    rate ladder — one ``f_acc:<wl>`` axis per accelerator — instead of the
    one shared ``f_acc`` axis (kept as the parity reference); ``acc_rates``
    may then also be a ``{workload name: ladder}`` mapping for
    heterogeneous ladders.  Restricted to all-islands-equal rates the
    independent sweep reproduces the shared sweep bit for bit (tested).

    ``positions`` defaults to every grid node except the MEM tile.  Joint
    placements where two accelerators collide are masked invalid (their
    objective entries are still computed — the arrays stay rectangular —
    but :meth:`SweepResult.pareto_indices` / ``topk_indices`` skip them).

    Throughput of a joint point is the sum of the accelerators' modeled
    throughputs; area sums each accelerator's replication cost; energy is
    the mean accelerator-island chip power (each island at its own rate)
    plus the NoC share, per unit of total throughput; ``mem_traffic`` sums
    each accelerator's offered MEM stream at its own island rate.

    **Chunked/streaming evaluation**: when ``chunk_points`` is given and
    the cross-product exceeds it, the grid is evaluated in fixed-size
    axis blocks (whole trailing-axis panels, so every block is a
    contiguous range of global flat indices) with a running Pareto/top-k
    merge, and a :class:`ChunkedSweepResult` is returned — peak memory is
    ~``41 * chunk_points`` bytes (five float64 objective/temp panels + a
    bool mask) however large the full grid is, while indices stay globally
    addressable and Pareto front / top-k are identical to a one-shot
    sweep (tested).  Otherwise a dense :class:`SweepResult` is returned.

    **Multi-device sharding**: ``devices=`` (``None`` / int / ``"auto"``,
    see :func:`repro.shard.resolve_devices`) switches each block (or the
    whole grid on the dense path) to a flat per-point jax evaluator that
    decodes the block's points itself, from the block's first coordinates
    and the axes' value tables, and whose point axis is
    ``shard_map``-partitioned across devices.  Any device
    count — including 1 — produces identical floats (sharding only splits
    elementwise math); ``devices=None`` keeps the numpy float64 path as
    the bit-for-bit ground truth, against which the jax float32 path
    deviates ~1e-6 relative.  Multi-device CPU runs need
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
    first jax import.

    **Physical DVFS** (``tech_node=`` / ``tech_variant=``): passing a node
    (int or sequence from :data:`repro.core.voltage.TECH_NODES`) and/or a
    scaling variant (``"itrs"``/``"cons"`` or a sequence) appends one
    trailing ``tech`` axis — the (node, variant) cross product — and
    switches the energy objective from the linear voltage proxy to the
    physical ``power_scl * (P_static + P_dyn f V̂(f)^2)`` model of
    :class:`repro.core.voltage.TechModel`.  Throughput/area/mem_traffic
    are tech-invariant (the grid anchors to the measured Table-I rates);
    the axis streams through ``chunk_points=`` and shards through
    ``devices=`` like any other.  ``tech_node=None`` (the default) keeps
    today's linear model bit for bit.
    """
    if isinstance(workloads, AccelWorkload):
        workloads = (workloads,)
    workloads = tuple(workloads)
    lay, axes, vals = _prepare_axes(model, workloads, ks, acc_rates,
                                    noc_rates, tg_rates, positions,
                                    island_rates, tech_node=tech_node,
                                    tech_variant=tech_variant)
    ndim = lay.ndim
    shape = tuple(len(v) for _, v in axes)
    n_points = int(np.prod([len(v) for _, v in axes], dtype=np.int64))

    n_devices = 0
    if devices is not None:
        from repro import shard as shard_mod
        n_devices = shard_mod.resolve_devices(devices)

    t0 = time.perf_counter()
    if chunk_points is None or n_points <= chunk_points:
        get = lambda dim, v: _axis(v, dim, ndim)    # noqa: E731
        if n_devices:
            out = _eval_flat_points(model, workloads, n_tg, lay, vals,
                                    shape, 0, n_points, get, shape,
                                    n_devices)
            for o in ("throughput", "energy_per_unit", "mem_traffic"):
                out[o] = out[o].astype(np.float64)
        else:
            out = _eval_grid(model, workloads, n_tg, lay, vals, get, shape)
        elapsed = time.perf_counter() - t0
        return SweepResult(
            axes=axes, shape=shape, workloads=workloads, n_tg=n_tg,
            throughput=out["throughput"].ravel(),
            area=out["area"].ravel(),
            energy_per_unit=out["energy_per_unit"].ravel(),
            valid=out["valid"].ravel(),
            mem_traffic=out["mem_traffic"].ravel(),
            elapsed_s=elapsed)

    # ---- chunked/streaming path: fixed-size blocks of whole trailing
    # panels; every block covers the contiguous global flat range
    # [o0*inner, o1*inner) so survivors carry global indices for free
    inner = 1
    s = ndim
    while s > 0 and inner * shape[s - 1] <= chunk_points:
        inner *= shape[s - 1]
        s -= 1
    outer_shape = shape[:s]
    outer_n = int(np.prod(outer_shape, dtype=np.int64)) if s else 1
    o_per_block = max(1, chunk_points // max(inner, 1))

    objs = [name for name, _ in _TRACKED_OBJECTIVES]
    empty = {"i": np.empty(0, dtype=np.int64),
             **{o: np.empty(0, dtype=np.float64) for o in objs}}
    front = dict(empty)
    topk = {o: dict(empty) for o in objs}
    n_valid = 0
    n_chunks = 0
    peak_bytes = 0

    for o0 in range(0, outer_n, o_per_block):
        o1 = min(o0 + o_per_block, outer_n)
        O = o1 - o0
        coords = np.unravel_index(np.arange(o0, o1), outer_shape)
        blk_ndim = ndim - s + 1

        def get(dim, v, coords=coords, O=O):
            v = np.asarray(v)
            if dim < s:
                return v[coords[dim]].reshape((O,) + (1,) * (ndim - s))
            bshape = [1] * blk_ndim
            bshape[dim - s + 1] = v.shape[0]
            return v.reshape(bshape)

        blk_shape = (O,) + shape[s:]
        with _span("sweep_chunk"):
            if n_devices:
                flat = _eval_flat_points(model, workloads, n_tg, lay, vals,
                                         shape, o0 * inner, o1 * inner,
                                         get, blk_shape, n_devices)
            else:
                out = _eval_grid(model, workloads, n_tg, lay, vals, get,
                                 blk_shape)
                flat = {k: v.ravel() for k, v in out.items()}
        n_chunks += 1
        peak_bytes = max(peak_bytes, sum(v.nbytes for v in flat.values())
                         + flat["throughput"].nbytes)   # + kernel temp

        # the block's valid rows folded into the running front and top-k
        with _span("sweep_front"):
            vpos = np.nonzero(flat["valid"])[0]
            n_valid += int(vpos.size)
            if vpos.size == 0:
                continue
            rows = {"i": o0 * inner + vpos,
                    **{o: flat[o][vpos].astype(np.float64, copy=False)
                       for o in objs}}

            pre = _front_prefilter(rows["throughput"], rows["area"],
                                   rows["energy_per_unit"])
            bf = pre[pareto_front_indices(rows["throughput"][pre],
                                          rows["area"][pre],
                                          rows["energy_per_unit"][pre])]
            front = _merge_front(front, {k: v[bf] for k, v in rows.items()})
            for o, maximize in _TRACKED_OBJECTIVES:
                key = -rows[o] if maximize else rows[o]
                sel = _topk_select(key, rows["i"], topk_track)
                cat = {k: np.concatenate([topk[o][k], v[sel]])
                       for k, v in rows.items()}
                ckey = -cat[o] if maximize else cat[o]
                keep = _topk_select(ckey, cat["i"], topk_track)
                topk[o] = {k: v[keep] for k, v in cat.items()}

    # assemble the tracked-survivor store: pareto ∪ top-k, deduped
    pools = [front] + [topk[o] for o in objs]
    all_idx = np.concatenate([p["i"] for p in pools])
    uniq, upos = np.unique(all_idx, return_index=True)
    cand_values = {o: np.concatenate([p[o] for p in pools])[upos]
                   for o in objs}
    elapsed = time.perf_counter() - t0
    return ChunkedSweepResult(
        axes=axes, shape=shape, workloads=workloads, n_tg=n_tg,
        n_points=n_points, n_valid=n_valid,
        cand_indices=uniq, cand_values=cand_values,
        pareto=np.sort(front["i"]),
        topk={o: topk[o]["i"] for o in objs},
        topk_track=topk_track, chunk_points=chunk_points,
        n_chunks=n_chunks, peak_chunk_bytes=int(peak_bytes),
        elapsed_s=elapsed)


# ---------------------------------------------------------------------------
# Closed-loop re-ranking: the static sweep meets the runtime simulator
# ---------------------------------------------------------------------------


@dataclass
class ClosedLoopScore:
    """Simulated runtime scores for a set of sweep survivors.

    ``indices`` are flat :class:`SweepResult` indices; the parallel arrays
    hold each point's simulated p99 latency, energy per request and
    sustained throughput under the replayed trace.  ``order`` re-ranks
    ``indices`` best-first: points meeting the p99 SLA sorted by energy
    per request, then SLA violators by how badly they miss it.

    ``results`` holds per-point ``sim.SimResult`` objects on the
    sequential path; on the batched path it holds the single
    ``sim.BatchSimResult`` of the one stacked replay.

    ``counters`` (only when ``observe=`` enabled the monitoring plane) is
    one ``sim.CounterPlane.summary()`` dict per survivor — utilization,
    stall fraction, NoC flits, per-island energy — aligned with
    ``indices``.
    """
    indices: np.ndarray                 # (M,) int64
    p99_latency_s: np.ndarray           # (M,) float64
    energy_per_request_j: np.ndarray    # (M,) float64
    throughput_rps: np.ndarray          # (M,) float64
    order: np.ndarray                   # (M,) int64 positions into indices
    results: List[object]               # SimResults, or one BatchSimResult
    drop_rate: Optional[np.ndarray] = None   # (M,) under a fault schedule
    counters: Optional[List[Dict[str, float]]] = None   # (M,) summaries

    def ranked_indices(self) -> np.ndarray:
        """Flat SweepResult indices, best-first."""
        return self.indices[self.order]


def _rank_scores(p99: np.ndarray, ept: np.ndarray,
                 p99_sla_s: Optional[float],
                 drop_rate: Optional[np.ndarray] = None,
                 max_drop_rate: Optional[float] = None) -> np.ndarray:
    """Best-first order: SLO-miss severity (p99 miss + drop-budget miss),
    then energy.  Without SLO bounds the legacy (energy, p99) order is
    unchanged; ``drop_rate`` only participates when given (fault-aware
    scoring), so fault-free rankings are untouched.

    Degenerate survivors — zero-completion runs reporting NaN energy per
    request and/or NaN p99 — always rank last via an explicit mask (their
    NaN channels carry no information, and ``np.lexsort``'s NaN placement
    in non-primary keys is not a contract we want to lean on)."""
    p99 = np.asarray(p99, dtype=np.float64)
    ept = np.asarray(ept, dtype=np.float64)
    degenerate = np.isnan(p99) | np.isnan(ept)
    p99 = np.where(degenerate, np.inf, p99)
    ept = np.where(degenerate, np.inf, ept)
    if p99_sla_s is not None or max_drop_rate is not None:
        miss = np.zeros_like(ept)
        if p99_sla_s is not None:
            miss = miss + np.maximum(0.0, p99 / p99_sla_s - 1.0)
        if max_drop_rate is not None and drop_rate is not None:
            miss = miss + np.maximum(0.0, drop_rate / max_drop_rate - 1.0)
        return np.lexsort((ept, miss, degenerate))   # SLO first, then energy
    if drop_rate is not None:
        # fault-aware but unbudgeted: robustness outranks energy
        return np.lexsort((ept, p99, drop_rate, degenerate))
    return np.lexsort((p99, ept, degenerate))  # energy first, p99 tie-break


@_spanned("closed_loop_score")
def closed_loop_score(result: SweepResult, trace, *,
                      model: SoCPerfModel,
                      indices: Optional[Sequence[int]] = None,
                      top: int = 8,
                      p99_sla_s: Optional[float] = None,
                      controller_factory=None,
                      batch_controller_factory=None,
                      req_mb: float = 0.1,
                      sim_config=None,
                      batch: Optional[bool] = None,
                      backend: str = "numpy",
                      trace_seed: int = 0,
                      flows=None,
                      balancer_factory=None,
                      fault_schedule=None,
                      slo=None,
                      max_drop_rate: Optional[float] = None,
                      observe=None,
                      devices=None,
                      tech=None
                      ) -> ClosedLoopScore:
    """Re-rank static-sweep survivors by *simulated* runtime behaviour.

    The static objectives of :func:`grid_sweep` assume steady saturated
    streams; under dynamic traffic two points with equal static throughput
    can have wildly different tail latency and idle-power profiles.  This
    bridge replays ``trace`` (a ``repro.sim.Trace`` whose destinations map
    1:1 to ``result.workloads``) through each survivor — by default the
    ``top`` throughput points of the Pareto front — with an optional
    online DFS controller in the loop, and ranks by (p99 SLA met, energy
    per request).  The static sweep and the runtime loop become one
    pipeline::

        res   = grid_sweep(model, wls, ...)
        score = closed_loop_score(res, diurnal_trace(...), model=model,
                                  p99_sla_s=0.05)
        best  = res.design_point(int(score.ranked_indices()[0]))

    **Batched by default**: the survivors are stacked into one
    ``repro.sim.BatchSimPlatform`` and replayed as a single array program
    (``backend="numpy"`` or ``"jax"`` for the ``lax.scan`` tick loop) —
    re-ranking ~1k survivors is one batched run, not ~1k sequential sims.
    ``batch_controller_factory`` receives the stacked platform and must
    return a ``repro.sim.BatchControllerHarness`` (or None).  Passing the
    legacy per-point ``controller_factory`` (a
    ``repro.sim.ControllerHarness`` per materialized ``SimPlatform``)
    selects the sequential path, as does ``batch=False``; the sequential
    path is the differential-test reference and produces identical
    rankings (tested).  ``devices=`` (``None`` / int / ``"auto"``) shards
    the batched jax scan's design axis across devices via ``shard_map`` —
    bitwise identical to the single-device jax run at any device count.

    Determinism: ``trace`` may be a callable ``trace(seed) -> Trace``; it
    is invoked with the explicit ``trace_seed``, so repeated scoring of
    the same survivors replays an identical trace instead of relying on
    whatever generator state the caller happened to have.  Imports
    ``repro.sim`` lazily — the core DSE layer stays importable without
    the simulation subsystem.

    Workload shape: ``flows`` (a ``repro.sim.FlowPattern``) scores the
    survivors under a tile-to-tile / pipeline workload instead of the
    default accelerator->MEM stream; ``balancer_factory`` (platform ->
    ``repro.sim.LoadBalancer``) puts a replica-group admission policy in
    the loop next to the DFS controller.  Both apply to the batched and
    the sequential path alike, so the differential reference covers them.
    On the batched path ``trace`` may also be a ``repro.sim.BatchTrace``
    whose design axis matches the survivor count — each survivor then
    replays its own arrival tensor.

    Robustness scoring: ``fault_schedule`` (a ``repro.sim.FaultSchedule``)
    replays every survivor through the same injected failures (tile
    kills, link degradation, stuck actuators) with ``slo`` (a
    ``repro.sim.SLOConfig``) fixing deadline/recovery semantics — the
    ranking then uses p99-*under-failure* and each survivor's drop rate
    (hard budget via ``max_drop_rate``, joining the p99 SLA in the miss
    score; otherwise as the primary sort key ahead of energy).  Fault-free
    calls rank exactly as before.

    Observability: ``observe`` (a ``repro.sim.Observer`` or a level name
    ``"counters"``/``"full"``) turns on the monitoring plane inside every
    replay; the score then carries one counter summary per survivor in
    ``ClosedLoopScore.counters`` (batched: one ``design(j)`` slice each of
    the single stacked plane).  ``observe=None`` keeps the replays
    monitoring-free and is bit-for-bit identical to pre-observability
    scoring.

    Physical DVFS: ``tech=`` (a ``repro.core.voltage.TechModel``, a node
    int, or a ``(node, variant)`` pair) replays every survivor under the
    physical ``V^2 f`` tick-energy model and clamps DFS commits to the
    node's legal ratio range — the re-ranking then reflects the tech
    node's energy landscape.  ``tech=None`` keeps the linear proxy bit
    for bit.
    """
    from repro.sim import BatchTrace, SimConfig, SimEngine, SimPlatform

    tech = TechModel.coerce(tech)
    if callable(trace):
        trace = trace(trace_seed)

    if indices is None:
        pf = result.pareto_indices()
        thr_pf = result.objective_values("throughput", pf)
        ordr = np.argsort(-thr_pf, kind="stable")
        indices = pf[ordr][:top]
    indices = np.asarray(indices, dtype=np.int64)

    if batch is None:
        batch = controller_factory is None
    assert not (batch and controller_factory is not None), \
        "per-point controller_factory requires batch=False"
    if isinstance(trace, BatchTrace):
        # each survivor replays its own tensor row — a silent mismatch
        # would pair survivor j with the wrong workload
        assert trace.n_designs == indices.shape[0], \
            (trace.n_designs, indices.shape[0])

    if batch:
        from repro.sim import BatchSimEngine, BatchSimPlatform
        with _span("cosim_build"):
            platform = BatchSimPlatform.from_design_points(
                model, result, indices, req_mb=req_mb, n_tg=result.n_tg,
                flows=flows)
            controller = (batch_controller_factory(platform)
                          if batch_controller_factory is not None else None)
            engine = BatchSimEngine(
                platform, config=sim_config or SimConfig(),
                controller=controller,
                balancer=(balancer_factory(platform)
                          if balancer_factory is not None else None),
                backend=backend, faults=fault_schedule, slo=slo,
                observe=observe, devices=devices, tech=tech)
        r = engine.run(trace)
        p99 = r.p99_latency_s
        ept = r.energy_per_request_j
        thr = r.throughput_rps
        drops = (np.asarray(r.drop_rate, dtype=np.float64)
                 if fault_schedule is not None else None)
        results: List[object] = [r]
        ob = engine.observer
        counters = (None if ob is None or ob.counters is None else
                    [ob.counters.design(j).summary()
                     for j in range(indices.shape[0])])
    else:
        p99 = np.empty(indices.shape[0])
        ept = np.empty(indices.shape[0])
        thr = np.empty(indices.shape[0])
        drops = (np.empty(indices.shape[0])
                 if fault_schedule is not None else None)
        results = []
        summaries: List[Dict[str, float]] = []
        for j, i in enumerate(indices):
            dp = result.design_point(int(i))
            platform = SimPlatform.from_design_point(
                model, dp, result.workloads, req_mb=req_mb,
                n_tg=result.n_tg, flows=flows)
            controller = (controller_factory(platform)
                          if controller_factory is not None else None)
            engine = SimEngine(platform,
                               config=sim_config or SimConfig(),
                               controller=controller,
                               balancer=(balancer_factory(platform)
                                         if balancer_factory is not None
                                         else None),
                               faults=fault_schedule, slo=slo,
                               observe=observe, tech=tech)
            r = engine.run(trace.design(j) if isinstance(trace, BatchTrace)
                           else trace)
            results.append(r)
            p99[j] = r.p99_latency_s
            ept[j] = r.energy_per_request_j
            thr[j] = r.throughput_rps
            if drops is not None:
                drops[j] = r.drop_rate
            if engine.observer is not None \
                    and engine.observer.counters is not None:
                # summarize NOW — a shared Observer instance re-attaches
                # its plane on the next survivor's run
                summaries.append(engine.observer.counters.summary())
        counters = summaries if len(summaries) == len(results) else None

    order = _rank_scores(p99, ept, p99_sla_s, drop_rate=drops,
                         max_drop_rate=max_drop_rate)
    return ClosedLoopScore(indices=indices, p99_latency_s=p99,
                           energy_per_request_j=ept, throughput_rps=thr,
                           order=np.asarray(order, dtype=np.int64),
                           results=results, drop_rate=drops,
                           counters=counters)


# ---------------------------------------------------------------------------
# Scalar reference sweep (original API)
# ---------------------------------------------------------------------------


def sweep_soc(model: SoCPerfModel, wl: AccelWorkload,
              *, ks: Sequence[int] = (1, 2, 4),
              noc_rates: Sequence[float] = (0.1, 0.5, 1.0),
              acc_rates: Sequence[float] = (0.2, 0.6, 1.0),
              positions: Sequence[Tuple[int, int]] = ((1, 1), (3, 3)),
              n_tg: int = 0) -> List[DesignPoint]:
    """Exhaustive scalar sweep over the paper's axes for one accelerator.

    The per-point reference path; :func:`grid_sweep` is the batched
    equivalent and is tested to match it within fp tolerance."""
    out: List[DesignPoint] = []
    for k, fn, fa, pos in itertools.product(ks, noc_rates, acc_rates,
                                            positions):
        w = dataclasses.replace(wl, replication=k)
        rates = {"acc": fa, "noc_mem": fn, "tg": 1.0}
        thr = model.accel_throughput(w, pos, rates, n_tg)
        area = replication_area_model(
            weight_bytes=1.0, act_bytes=0.5, k=k)["total_bytes_per_dev"]
        power = chip_power(fa, busy=1.0) \
            + NOC_POWER_SHARE * chip_power(fn, busy=1.0)
        out.append(DesignPoint(
            replication={wl.name: k}, rates=rates,
            placement={wl.name: pos}, throughput=thr, area=area,
            energy_per_unit=power / max(thr, 1e-9)))
    return out


def sweep_replication_roofline(eval_cell: Callable[[int], Dict[str, float]],
                               ks: Sequence[int] = (1, 2, 4, 8)
                               ) -> List[Dict[str, float]]:
    """Pod-scale MRA sweep: ``eval_cell(K)`` lowers/compiles the cell on the
    K-factored mesh and returns roofline terms; used by §Perf hillclimbs."""
    rows = []
    for k in ks:
        r = dict(eval_cell(k))
        r["K"] = k
        r["predicted_gain"] = replication_throughput_model(k)
        rows.append(r)
    return rows


def summarize(points: Sequence[DesignPoint], top: int = 10) -> str:
    front = pareto_front(points)
    front.sort(key=lambda p: -p.throughput)
    lines = [f"{len(points)} points, {len(front)} on Pareto front"]
    for p in front[:top]:
        lines.append(
            f"  K={p.replication}  rates={ {k: round(v, 2) for k, v in p.rates.items()} }"
            f"  pos={p.placement}  thr={p.throughput:.2f}  area={p.area:.2f}"
            f"  E/u={p.energy_per_unit:.1f}")
    return "\n".join(lines)


def summarize_result(res, top: int = 10) -> str:
    """Summary of a batched sweep (dense or chunked) without materializing
    all points."""
    front_idx = res.pareto_indices()
    order = np.argsort(-res.objective_values("throughput", front_idx),
                       kind="stable")
    lines = [f"{len(res)} points ({res.n_valid} valid, "
             f"{res.points_per_second:,.0f} pts/s), "
             f"{front_idx.shape[0]} on Pareto front"]
    for p in res.design_points(front_idx[order][:top]):
        lines.append(
            f"  K={p.replication}  rates={ {k: round(v, 2) for k, v in p.rates.items()} }"
            f"  pos={p.placement}  thr={p.throughput:.2f}  area={p.area:.2f}"
            f"  E/u={p.energy_per_unit:.1f}")
    return "\n".join(lines)
