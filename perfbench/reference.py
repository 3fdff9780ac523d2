"""Plain reference of the SoC model the benchmark holds the program to.

Two computations, written from the model's equations in straightforward
NumPy and imported from nothing of the program under test:

* :func:`sweep` evaluates every point of a design space (throughput,
  area, energy per unit, memory traffic, placement validity) and returns
  the Pareto front and the per-objective top-k;
* :func:`cosim` replays a request trace through a batch of designs, one
  fluid-queue tick at a time, with PID DFS and a queue guard in the loop,
  tile kills, stuck actuators and SLO deadline drops, and reconstructs
  each design's latency percentiles and its ranking.

Everything runs in float64.  ``prec`` rounds every intermediate result to
a lower precision instead (:data:`BF16`): the control that the comparison
in :mod:`compare` has to fail.

The flat index of a design point is the C-order position over the axes of
:func:`space_axes` (``K:<accel>`` ... ``f_noc``, ``f_acc`` (shared) or
``f_acc:<accel>`` ..., ``f_tg``, ``pos:<accel>`` ...), the order the
program's sweep documents; it is how the two sides name the same design.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np

# ---------------------------------------------------------------------------
# precision of the intermediate results
# ---------------------------------------------------------------------------


class Precision:
    """Round each intermediate to ``dtype`` (identity for float64)."""

    def __init__(self, dtype=None):
        self.dtype = None if dtype is None else np.dtype(dtype)

    def __call__(self, x):
        if self.dtype is None:
            return np.asarray(x, dtype=np.float64)
        return np.asarray(x, dtype=np.float64).astype(self.dtype).astype(
            np.float64)


F64 = Precision()
BF16 = Precision(ml_dtypes.bfloat16)


# ---------------------------------------------------------------------------
# the deployment: a configuration file's model section
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Model:
    rows: int
    cols: int
    link_bw: float
    hop_latency: float
    max_slowdown: float
    mem_pos: Tuple[int, int]
    ref_pos: Tuple[int, int]
    mem_service: float
    tg_demand: float
    tg_demand_fig4: float
    own_demand: float
    hop_latency_share: float
    p_static_w: float
    p_dyn_w: float
    v_base: float
    v_slope: float
    noc_power_share: float
    area_weight_bytes: float
    area_act_bytes: float
    area_model: float
    names: Tuple[str, ...]
    base_mbps: Tuple[float, ...]
    wire_share: Tuple[float, ...]

    @classmethod
    def from_config(cls, cfg: dict) -> "Model":
        noc, m, pw, ar = cfg["noc"], cfg["model"], cfg["power"], cfg["area"]
        acc = cfg["accelerators"]
        return cls(rows=int(noc["rows"]), cols=int(noc["cols"]),
                   link_bw=float(noc["link_bw"]),
                   hop_latency=float(noc["hop_latency"]),
                   max_slowdown=float(noc["max_slowdown"]),
                   mem_pos=tuple(cfg["mem_pos"]),
                   ref_pos=tuple(m["ref_pos"]),
                   mem_service=float(m["mem_service"]),
                   tg_demand=float(m["tg_demand"]),
                   tg_demand_fig4=float(m["tg_demand_fig4"]),
                   own_demand=float(m["own_demand"]),
                   hop_latency_share=float(m["hop_latency_share"]),
                   p_static_w=float(pw["p_static_w"]),
                   p_dyn_w=float(pw["p_dyn_w"]),
                   v_base=float(pw["v_base"]), v_slope=float(pw["v_slope"]),
                   noc_power_share=float(pw["noc_power_share"]),
                   area_weight_bytes=float(ar["weight_bytes"]),
                   area_act_bytes=float(ar["act_bytes"]),
                   area_model=float(ar["model"]),
                   names=tuple(a["name"] for a in acc),
                   base_mbps=tuple(float(a["base_mbps"]) for a in acc),
                   wire_share=tuple(float(a["wire_share"]) for a in acc))

    @property
    def A(self) -> int:
        return len(self.names)

    def route(self, src, dst) -> List[Tuple[Tuple[int, int], Tuple[int, int]]]:
        """XY route on the mesh: along the row to the destination column,
        then along the column; one (from, to) link per hop."""
        (r, c), (dr, dc) = tuple(src), tuple(dst)
        links = []
        while c != dc:
            nc = c + (1 if dc > c else -1)
            links.append(((r, c), (r, nc)))
            c = nc
        while r != dr:
            nr = r + (1 if dr > r else -1)
            links.append(((r, c), (nr, c)))
            r = nr
        return links

    def hops(self, src, dst) -> int:
        return abs(src[0] - dst[0]) + abs(src[1] - dst[1])

    def power(self, f, busy, prec=F64):
        v = prec(self.v_base + self.v_slope * f)
        return prec(self.p_static_w + prec(prec(self.p_dyn_w * f) * prec(
            v * v)) * busy)


def ladder(spec) -> Tuple[float, ...]:
    """A DFS ladder ``[f_min, f_max, step]`` in MHz as rates f / f_max.
    The configuration's ``ladders`` are the actuators' ladders; its space
    sweeps every ``acc_stride``-th and ``noc_stride``-th level of them."""
    lo, hi, step = (int(x) for x in spec)
    return tuple(m / hi for m in range(lo, hi + 1, step))


def space_axes(cfg: dict) -> List[Tuple[str, tuple]]:
    """The swept axes of a configuration, in flat-index order."""
    sp = cfg["space"]
    names = [a["name"] for a in cfg["accelerators"]]
    acc = ladder(cfg["ladders"][sp["acc_ladder"]])[::sp.get("acc_stride", 1)]
    noc = ladder(cfg["ladders"][sp["noc_ladder"]])[::sp.get("noc_stride", 1)]
    pos = sp.get("positions")
    if pos is None:
        mem = tuple(cfg["mem_pos"])
        pos = [(r, c) for r in range(cfg["noc"]["rows"])
               for c in range(cfg["noc"]["cols"]) if (r, c) != mem]
    pos = tuple(tuple(int(v) for v in p) for p in pos)
    axes = [(f"K:{n}", tuple(int(k) for k in sp["ks"])) for n in names]
    axes.append(("f_noc", noc))
    if sp["island_rates"] == "independent":
        axes += [(f"f_acc:{n}", acc) for n in names]
    else:
        axes.append(("f_acc", acc))
    axes.append(("f_tg", tuple(float(f) for f in sp["tg_rates"])))
    axes += [(f"pos:{n}", pos) for n in names]
    return axes


def decode(cfg: dict, indices) -> Dict[str, np.ndarray]:
    """Design parameters of flat indices: ``k`` (B, A), ``pos`` (B, A, 2),
    ``f_acc`` (B, A), ``f_noc`` (B,), ``f_tg`` (B,)."""
    axes = space_axes(cfg)
    shape = tuple(len(v) for _, v in axes)
    idx = np.asarray(indices, dtype=np.int64)
    coords = dict(zip((n for n, _ in axes), np.unravel_index(idx, shape)))
    vals = {n: np.asarray(v) for n, v in axes}
    names = [a["name"] for a in cfg["accelerators"]]
    shared = "f_acc" in vals

    def ax(name):
        return vals[name][coords[name]]

    return {"k": np.stack([ax(f"K:{n}") for n in names], -1).astype(float),
            "pos": np.stack([ax(f"pos:{n}") for n in names], -2).astype(int),
            "f_acc": np.stack([ax("f_acc" if shared else f"f_acc:{n}")
                               for n in names], -1).astype(float),
            "f_noc": ax("f_noc").astype(float),
            "f_tg": ax("f_tg").astype(float)}


# ---------------------------------------------------------------------------
# the design-space sweep
# ---------------------------------------------------------------------------


def objectives(m: Model, n_tg: float, k, f_acc, pos, f_noc, f_tg,
               prec=F64) -> Dict[str, np.ndarray]:
    """Objectives of design points given as broadcastable arrays:
    ``k``, ``f_acc``, ``pos`` are sequences of one array per accelerator
    (``pos`` of ``(..., 2)`` grid coordinates)."""
    A = m.A
    fn = prec(np.maximum(f_noc, 1e-3))
    load = prec(m.own_demand + prec(m.tg_demand * f_tg) * n_tg)
    slow = prec(np.maximum(1.0, prec(load / prec(m.link_bw * fn))))
    ref_hopf = 1.0 + m.hop_latency_share * m.hops(m.ref_pos, m.mem_pos)
    thr = 0.0
    for a in range(A):
        w = m.wire_share[a]
        hops = (np.abs(pos[a][..., 0] - m.mem_pos[0])
                + np.abs(pos[a][..., 1] - m.mem_pos[1]))
        hopf = prec(1.0 + m.hop_latency_share * hops)
        fa = prec(np.maximum(f_acc[a], 1e-3))
        t = prec(prec((1.0 - w) / prec(k[a] * fa))
                 + prec(prec(prec(w * slow) * hopf) / fn))
        t0 = (1.0 - w) + w * max(1.0, m.own_demand) * ref_hopf
        thr = prec(thr + prec(prec(m.base_mbps[a] * t0) / t))
    pw = 0.0
    for a in range(A):
        pw = prec(pw + m.power(f_acc[a], 1.0, prec))
    power = prec(prec(pw / A) + prec(m.noc_power_share
                                     * m.power(f_noc, 1.0, prec)))
    energy = prec(power / np.maximum(thr, 1e-9))
    offer = 0.0
    for a in range(A):
        offer = prec(offer + np.minimum(1.0, prec(5.0 * f_acc[a])))
    mem = np.minimum(prec(m.mem_service * f_noc),
                     prec(prec(prec(m.tg_demand_fig4 * f_tg) * n_tg)
                          + prec(offer * np.minimum(1.0, f_noc))))
    area = 0.0
    for a in range(A):
        area = area + (m.area_weight_bytes * k[a]
                       + m.area_act_bytes) / m.area_model
    valid = True
    for a in range(A):
        for b in range(a + 1, A):
            valid = valid & np.any(pos[a] != pos[b], axis=-1)
    return {"throughput": thr, "area": area, "energy_per_unit": energy,
            "mem_traffic": prec(mem), "valid": valid}


def point_objectives(cfg: dict, indices, prec=F64) -> Dict[str, np.ndarray]:
    """Objectives of the given flat indices."""
    m = Model.from_config(cfg)
    d = decode(cfg, indices)
    A = m.A
    return objectives(m, float(cfg["space"]["n_tg"]),
                      [d["k"][:, a] for a in range(A)],
                      [d["f_acc"][:, a] for a in range(A)],
                      [d["pos"][:, a] for a in range(A)],
                      d["f_noc"], d["f_tg"], prec)


OBJECTIVES = (("throughput", True), ("area", False),
              ("energy_per_unit", False), ("mem_traffic", False))


def pareto(thr, area, energy) -> np.ndarray:
    """Positions of the Pareto front: maximise throughput, minimise area
    and energy; a point is dominated by one at least as good in all three
    and strictly better in one (equal points do not dominate each other).
    Per area value a two-objective staircase prunes, then every pair of
    the remaining candidates is compared."""
    cand = []
    for av in np.unique(area):
        sel = np.nonzero(area == av)[0]
        t, e = thr[sel], energy[sel]
        order = np.lexsort((e, -t))
        t, e, sel = t[order], e[order], sel[order]
        starts = np.concatenate(([0], np.nonzero(np.diff(t))[0] + 1))
        gmin = np.minimum.reduceat(e, starts)              # per equal-thr group
        before = np.concatenate(([np.inf], np.minimum.accumulate(gmin)[:-1]))
        grp = np.repeat(np.arange(starts.size), np.diff(
            np.concatenate((starts, [t.size]))))
        keep = ~((before[grp] <= e) | (gmin[grp] < e))
        cand.append(sel[keep])
    c = np.sort(np.concatenate(cand)) if cand else np.empty(0, np.int64)
    t, a, e = thr[c], area[c], energy[c]
    dominated = np.zeros(c.size, dtype=bool)
    for s in range(0, c.size, 1024):
        ge = ((t[None, :] >= t[s:s + 1024, None])
              & (a[None, :] <= a[s:s + 1024, None])
              & (e[None, :] <= e[s:s + 1024, None]))
        strict = ((t[None, :] > t[s:s + 1024, None])
                  | (a[None, :] < a[s:s + 1024, None])
                  | (e[None, :] < e[s:s + 1024, None]))
        dominated[s:s + 1024] = (ge & strict).any(axis=1)
    return c[~dominated]


def topk(values, index, k: int, maximize: bool) -> np.ndarray:
    """Positions of the k best values, best first, ties by index."""
    key = -values if maximize else values
    k = min(k, key.size)
    if k == 0:
        return np.empty(0, np.int64)
    edge = np.partition(key, k - 1)[k - 1]
    cand = np.nonzero(key <= edge)[0]
    return cand[np.lexsort((index[cand], key[cand]))[:k]]


def sweep(cfg: dict, k_top: int, prec=F64, block: int = 2_000_000) -> dict:
    """The whole space: its point count, valid count, Pareto front and
    top-``k_top`` per objective (flat indices, best first), with every
    objective's values on those points."""
    axes = space_axes(cfg)
    n = int(np.prod([len(v) for _, v in axes], dtype=np.int64))
    keep_i, keep_o = [], {o: [] for o, _ in OBJECTIVES}
    n_valid = 0
    for lo in range(0, n, block):
        idx = np.arange(lo, min(lo + block, n), dtype=np.int64)
        ob = point_objectives(cfg, idx, prec)
        v = np.nonzero(ob["valid"])[0]
        n_valid += v.size
        vi = idx[v]
        vals = {o: np.broadcast_to(ob[o], idx.shape)[v] for o, _ in OBJECTIVES}
        sel = [pareto(vals["throughput"], vals["area"],
                      vals["energy_per_unit"])]
        sel += [topk(vals[o], vi, k_top, mx) for o, mx in OBJECTIVES]
        s = np.unique(np.concatenate(sel))
        keep_i.append(vi[s])
        for o, _ in OBJECTIVES:
            keep_o[o].append(vals[o][s])
    ids = np.concatenate(keep_i)
    vals = {o: np.concatenate(v) for o, v in keep_o.items()}
    front = ids[pareto(vals["throughput"], vals["area"],
                       vals["energy_per_unit"])]
    top = {o: ids[topk(vals[o], ids, k_top, mx)] for o, mx in OBJECTIVES}
    return {"n_points": n, "n_valid": int(n_valid), "pareto": np.sort(front),
            "topk": top, "indices": ids, "values": vals}


# ---------------------------------------------------------------------------
# the closed-loop co-simulation
# ---------------------------------------------------------------------------


@dataclass
class Designs:
    """B designs on one platform: one island per accelerator, then the
    NoC+memory island."""
    k: np.ndarray           # (B, A)
    pos: np.ndarray         # (B, A, 2)
    rates: np.ndarray       # (B, I) initial island rates, I = A + 1
    f_tg: np.ndarray        # (B,)


def designs(cfg: dict, indices) -> Designs:
    d = decode(cfg, indices)
    return Designs(k=d["k"], pos=d["pos"],
                   rates=np.concatenate([d["f_acc"], d["f_noc"][:, None]],
                                        axis=1),
                   f_tg=d["f_tg"])


def _incidence(m: Model, pos: np.ndarray):
    """(B, A, L) 0/1 route->link incidence of each tile's stream to MEM,
    and (B, A) hop counts."""
    links: Dict[tuple, int] = {}
    B, A = pos.shape[:2]
    routes = [[m.route(tuple(pos[b, a]), m.mem_pos) for a in range(A)]
              for b in range(B)]
    for rb in routes:
        for r in rb:
            for ln in r:
                links.setdefault(ln, len(links))
    inc = np.zeros((B, A, max(len(links), 1)))
    hops = np.zeros((B, A))
    for b, rb in enumerate(routes):
        for a, r in enumerate(rb):
            hops[b, a] = len(r)
            for ln in r:
                inc[b, a, links[ln]] = 1.0
    return inc, hops


def service(m: Model, dz: Designs, rates, n_tg: float, hops, prec=F64):
    """(t_comp, t_wire, t_ref, f_tile, f_noc) at island rates (B, I)."""
    A = m.A
    w = np.asarray(m.wire_share)[None, :]
    f_tile = rates[:, :A]
    f_noc = rates[:, A]
    fa = prec(np.maximum(f_tile, 1e-3))
    fn = prec(np.maximum(f_noc, 1e-3))[:, None]
    load = prec(m.own_demand + prec(prec(m.tg_demand * dz.f_tg[:, None])
                                    * n_tg))
    slow = prec(np.maximum(1.0, prec(load / prec(m.link_bw * fn))))
    hopf = prec(1.0 + m.hop_latency_share * hops)
    t_comp = prec((1.0 - w) / prec(dz.k * fa))
    t_wire = prec(prec(prec(w * slow) * hopf) / fn)
    ref_hopf = 1.0 + m.hop_latency_share * m.hops(m.ref_pos, m.mem_pos)
    t_ref = (1.0 - w) + w * max(1.0, m.own_demand) * ref_hopf
    return t_comp, t_wire, t_ref, f_tile, f_noc


def capacity_rps(m: Model, dz: Designs, n_tg: float, req_mb: float):
    """(B, A) uncontended service capacity at the initial rates."""
    _, hops = _incidence(m, dz.pos)
    t_comp, t_wire, t_ref, _, _ = service(m, dz, dz.rates, n_tg, hops)
    return np.asarray(m.base_mbps)[None, :] * t_ref / (t_comp + t_wire) \
        / req_mb


@dataclass(frozen=True)
class Control:
    """PID rate policy plus queue guard, every ``interval`` ticks."""
    interval: int
    target: float
    kp: float
    ki: float
    kd: float
    min_rate: float
    integral_clamp: float
    guard_ticks: float
    guard_release_ticks: float
    guard_rate: float


@dataclass(frozen=True)
class Faults:
    """Per-tick fault masks: ``alive`` (T, A) tile availability,
    ``stuck`` (T, I) commits refused, ``stuck_rate`` (T, I) hardware rate
    (NaN: follows the committed rate)."""
    alive: Optional[np.ndarray]
    stuck: Optional[np.ndarray]
    stuck_rate: Optional[np.ndarray]

    @classmethod
    def build(cls, events: Sequence[dict], T: int, names: Sequence[str]):
        names = list(names)
        I = len(names) + 1
        alive = np.ones((T, len(names)))
        stuck = np.zeros((T, I), dtype=bool)
        srate = np.full((T, I), np.nan)
        for ev in events:
            s = min(max(int(ev["start"]), 0), T)
            e = T if ev.get("end") is None else min(max(int(ev["end"]), s), T)
            if ev["kind"] == "kill_tile":
                alive[s:e, names.index(ev["tile"])] = 0.0
            elif ev["kind"] == "stick_island":
                i = names.index(ev["island"])
                stuck[s:e, i] = True
                if ev.get("rate") is not None:
                    srate[s:e, i] = float(ev["rate"])
            else:
                raise ValueError(f"unknown fault kind {ev['kind']!r}")
        return cls(alive=alive if (alive < 1).any() else None,
                   stuck=stuck if stuck.any() else None,
                   stuck_rate=srate if np.isfinite(srate).any() else None)


def _quantize(levels: List[np.ndarray], req: np.ndarray) -> np.ndarray:
    """Nearest ladder level per (design, island), first level on ties;
    NaN stays NaN."""
    out = np.full(req.shape, np.nan)
    for i, lv in enumerate(levels):
        r = req[:, i]
        ok = ~np.isnan(r)
        j = np.argmin(np.abs(lv[None, :] - r[ok, None]), axis=1)
        out[ok, i] = lv[j]
    return out


def cosim(m: Model, dz: Designs, arrivals: np.ndarray, *, dt: float,
          n_tg: float, req_mb: float, ladders: Sequence[Sequence[float]],
          control: Control, faults: Optional[Faults] = None,
          deadline_s: Optional[float] = None, drain_dead: bool = False,
          prec=F64) -> dict:
    """Replay the (T, A) trace through every design.

    Returns per-design ``completed``, ``energy``, ``dropped`` (SLO plus
    drained work), ``swaps`` and the (T, B, A) ``admitted``/``served``/
    ``exits_unserved`` histories."""
    B, A = dz.k.shape
    T = arrivals.shape[0]
    I = A + 1
    inc, hops = _incidence(m, dz.pos)
    base = np.asarray(m.base_mbps)[None, :]
    t_comp_ref = (1.0 - np.asarray(m.wire_share)[None, :]) / dz.k
    levels = [np.asarray(lv, dtype=np.float64) for lv in ladders]
    skip = np.zeros(I, dtype=bool)
    skip[A] = True                      # the NoC+memory island: no tiles
    alive_all = faults.alive if faults is not None else None
    stuck_all = faults.stuck if faults is not None else None
    srate_all = faults.stuck_rate if faults is not None else None
    deadline_ticks = None if deadline_s is None else deadline_s / dt

    rates = dz.rates.astype(np.float64).copy()
    queue = np.zeros((B, A))
    busy = np.zeros((B, A))
    ctl_busy = np.zeros((B, A))
    energy = np.zeros(B)
    dropped = np.zeros(B)
    swaps = np.zeros(B, dtype=np.int64)
    guard = np.zeros((B, I), dtype=bool)
    integ = np.zeros((B, I))
    prev_err = np.zeros((B, I))
    has_prev = False
    adm_h = np.zeros((T, B, A))
    srv_h = np.zeros((T, B, A))
    gone_h = np.zeros((T, B, A))

    for t in range(T):
        alive = alive_all[t] if alive_all is not None else None
        dead_i = None
        if alive is not None:
            dead_i = np.concatenate([alive == 0.0, [False]])
        r_eff = rates
        if srate_all is not None:
            r_eff = np.where(np.isnan(srate_all[t])[None, :], rates,
                             srate_all[t][None, :])
        t_comp, t_wire, t_ref, f_tile, f_noc = service(m, dz, r_eff, n_tg,
                                                       hops, prec)
        if alive is not None and drain_dead:
            stranded = prec(queue * (1.0 - alive))
            queue = prec(queue - stranded)
            dropped = prec(dropped + stranded.sum(axis=-1))
            gone_h[t] += stranded
        arr = np.broadcast_to(arrivals[t], (B, A))
        q = prec(queue + arr)
        loads = prec(((m.own_demand * busy)[:, :, None] * inc).sum(axis=1))
        rho = prec(prec((inc * loads[:, None, :]).max(axis=-1))
                   / prec(m.link_bw * f_noc)[:, None])
        r = np.minimum(rho, 0.999)
        dyn = np.minimum(prec(1.0 + prec(r / prec(2.0 * (1.0 - r)))),
                         m.max_slowdown)
        cap = prec(prec(prec(base * t_ref) / prec(t_comp + prec(t_wire * dyn)))
                   / req_mb * dt)
        cap_nominal = cap
        if alive is not None:
            cap = prec(cap * alive)
        served = np.minimum(q, cap)
        queue = prec(q - served)
        busy = prec(np.where(cap > 0.0, served / np.where(cap > 0.0, cap, 1.0),
                             0.0))
        if deadline_ticks is not None:
            drop = prec(np.maximum(prec(queue - prec(cap_nominal
                                                     * deadline_ticks)), 0.0))
            queue = prec(queue - drop)
            dropped = prec(dropped + drop.sum(axis=-1))
            gone_h[t] += drop
        tp = m.power(f_tile, busy, prec)
        if alive is not None:
            tp = prec(tp * alive)
        noc_p = prec(m.noc_power_share * m.power(f_noc, 1.0, prec))
        energy = prec(energy + prec(prec(tp.sum(axis=-1) + noc_p) * dt))
        ctl_busy = prec(ctl_busy + busy)
        adm_h[t] = arr
        srv_h[t] = served

        if (t + 1) % control.interval == 0:
            util = np.concatenate([prec(ctl_busy / control.interval),
                                   np.zeros((B, 1))], axis=1)
            err = prec(np.where(skip[None, :], 0.0, util - control.target))
            i_term = np.clip(prec(integ + err), -control.integral_clamp,
                             control.integral_clamp)
            d_term = prec(err - prev_err) if has_prev else np.zeros_like(err)
            new = prec(prec(prec(rates + prec(control.kp * err))
                            + prec(control.ki * i_term))
                       + prec(control.kd * d_term))
            req = np.clip(new, control.min_rate, 1.0)
            req[:, skip] = np.nan
            integ, prev_err, has_prev = i_term, err, True
            qt = prec(queue / np.maximum(cap, 1e-12))
            worst = np.concatenate([qt, np.zeros((B, 1))], axis=1)
            latch = np.where(worst > control.guard_ticks, True,
                             np.where(worst < control.guard_release_ticks,
                                      False, guard))
            if dead_i is not None:
                latch &= ~dead_i[None, :]
            guard = latch
            req = np.where(latch, control.guard_rate, req)
            qz = _quantize(levels, req)
            changed = ~np.isnan(req) & (qz != rates)
            if dead_i is not None:
                changed &= ~dead_i[None, :]
            if stuck_all is not None:
                changed &= ~stuck_all[t][None, :]
            rates = np.where(changed, qz, rates)
            swaps += changed.any(axis=1)
            ctl_busy = np.zeros((B, A))

    return {"completed": srv_h.sum(axis=(0, 2)), "energy": energy,
            "dropped": dropped, "swaps": swaps, "admitted": adm_h,
            "served": srv_h, "exits_unserved": gone_h,
            "offered": float(arrivals.sum())}


def weighted_percentile(values, weights, q: float) -> float:
    """The smallest value whose cumulative weight reaches q% of the total."""
    keep = weights > 0
    v, w = values[keep], weights[keep]
    if v.size == 0:
        return math.nan
    order = np.argsort(v, kind="stable")
    v, cum = v[order], np.cumsum(w[order])
    i = np.searchsorted(cum, q / 100.0 * cum[-1], side="left")
    return float(v[min(i, v.size - 1)])


def latency_percentile(admitted, served, gone, dt: float, q: float) -> float:
    """Request-weighted latency percentile of one design from its (T, A)
    histories of FIFO fluid queues: each tick's arrivals leave when the
    cumulative exits (served plus dropped) pass their mid-rank."""
    T, A = admitted.shape
    ticks = np.arange(T, dtype=np.float64)
    vals, wts = [], []
    for a in range(A):
        n = admitted[:, a]
        mid = np.cumsum(n) - 0.5 * n
        depart = np.searchsorted(np.cumsum(served[:, a] + gone[:, a]), mid,
                                 side="left")
        done = (depart < T) & (n > 0)
        vals.append(((depart - ticks + 0.5) * dt)[done])
        wts.append(n[done])
    v, w = np.concatenate(vals), np.concatenate(wts)
    if v.size == 0 or w.sum() <= 0:
        return math.nan
    return weighted_percentile(v, w, q)


def score(sim: dict, dt: float, *, p99_sla_s: Optional[float] = None,
          max_drop_rate: Optional[float] = None,
          faulted: bool = False) -> dict:
    """p99, energy per request, drop rate and the best-first ranking:
    designs that miss the SLO (p99 over the limit, drop rate over the
    budget) rank after those that meet it, by how far they miss; then by
    energy per request.  Without limits a fault-free run ranks by energy,
    then p99.  Designs that completed nothing rank last."""
    B = sim["completed"].shape[0]
    p99 = np.array([latency_percentile(sim["admitted"][:, b],
                                       sim["served"][:, b],
                                       sim["exits_unserved"][:, b], dt, 99.0)
                    for b in range(B)])
    c = sim["completed"]
    ept = np.where(c > 0, sim["energy"] / np.maximum(c, 1e-9), np.nan)
    off = sim["offered"]
    drop = sim["dropped"] / off if off > 0 else np.zeros(B)
    bad = np.isnan(p99) | np.isnan(ept)
    p99k = np.where(bad, np.inf, p99)
    eptk = np.where(bad, np.inf, ept)
    miss = np.zeros(B)
    if p99_sla_s is not None or max_drop_rate is not None:
        if p99_sla_s is not None:
            miss = miss + np.maximum(0.0, p99k / p99_sla_s - 1.0)
        if max_drop_rate is not None and faulted:
            miss = miss + np.maximum(0.0, drop / max_drop_rate - 1.0)
        order = np.lexsort((eptk, miss, bad))
    elif faulted:
        raise NotImplementedError("a faulted run is ranked against limits")
    else:
        order = np.lexsort((p99k, eptk, bad))
    return {"p99": p99, "ept": ept, "drop_rate": drop, "miss": miss,
            "bad": bad, "order": order}
