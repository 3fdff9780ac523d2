"""Run-time monitoring infrastructure for the co-sim engines.

The paper's third pillar (next to accelerator replication and per-island
DFS) is a dedicated monitoring subsystem exposing "a variety of statistics
related to the traffic on the interconnect and the accelerators'
performance at run time".  This module is that subsystem for the
reproduction, shared by all three engines (sequential ``engine.py``,
batched NumPy ``batch.py``, and the jitted ``lax.scan`` backend):

* :class:`CounterPlane` — the hardware-counter plane: per-accelerator
  performance counters (invocations, busy/stall ticks, offered work,
  effective-vs-nominal capacity, hop-weighted traffic, contention
  exposure), per-link NoC counters (flit traffic, utilization integral,
  peak utilization), and the per-island energy integral.  Counters are
  windowed via :meth:`CounterPlane.reset`, which mirrors the
  ``manual_reset(counters, tiles=, kinds=)`` scoping semantics
  ``core/monitor.py`` established for the C3 monitor.
* :class:`ControlTrace` + :class:`TraceEvent` — structured control-plane
  tracing: schema'd, monotonically tick-stamped events for DFS
  commits/guard discards, load-balancer splits, fault transitions,
  detector belief flips, and SLO-drop spans, in a ring-bounded store with
  JSONL export (replacing the ad-hoc ``Telemetry.event`` dict soup).
* :class:`Observer` — the engine-facing façade with the ``level=`` knob
  (``"off"`` / ``"counters"`` / ``"full"``) so ``closed_loop_score`` can
  run thousands of designs with counters on and tracing off.
* :class:`Profiler` / :func:`profiled` — the program's span and counter
  recorder, always on: per-phase wall-clock totals, a bounded ring of
  :class:`Span` records (name, start, end, enclosing span) on the clock
  of the JAX profiler's host events, a
  ``jax.profiler.TraceAnnotation`` per open span so a traced run shows
  the span beside the device ops, and a ``gc_full`` span per full
  garbage collection.  The sweep and co-sim drivers record into it
  (``grid_sweep``, ``sweep_*``, ``closed_loop_score``, ``cosim_*``, the
  ``tick_loop_builds`` counter); the benchmark's per-layer readers
  (``perfbench/metrics``, through ``perfbench/spans.py``) and
  ``BatchSimResult.elapsed_wall_s`` read it.

Zero-perturbation contract: everything here only *reads* the arrays
``tick_step`` already computes.  The sequential engine uses the
:class:`DeferredCapture` (two preallocated slot-writes per tick, full
vectorized reconstruction after the run); the batched NumPy engine uses
the :class:`IncrementalCapture` (per-tick adds, cheap next to its
``(B, A, L)`` einsum); the jax backend carries plain accumulators through
the scan and builds the plane post-hoc via :meth:`CounterPlane.from_arrays`.
Simulated numerics are bit-for-bit identical with monitoring on or off.
"""

from __future__ import annotations

import gc
import itertools
import json
import sys
import threading
import time
from collections import deque
from contextlib import ContextDecorator
from dataclasses import dataclass, field
from typing import (Deque, Dict, Iterable, List, Mapping, NamedTuple,
                    Optional, Sequence, Tuple)

import numpy as np

from repro.core.perfmodel import chip_power
from repro.sim.telemetry import _json_safe

__all__ = [
    "LEVELS",
    "TRACE_KINDS",
    "TraceEvent",
    "ControlTrace",
    "CounterPlane",
    "DeferredCapture",
    "IncrementalCapture",
    "Observer",
    "Profiler",
    "Span",
    "RING_CAPACITY",
    "profiled",
    "get_profiler",
    "reset_profiler",
    "export_metrics",
]

LEVELS = ("off", "counters", "full")

PKT_BYTES = 512.0   # matches engine.py / core/monitor.py

# ---------------------------------------------------------------------------
# Control-plane trace
# ---------------------------------------------------------------------------

#: The trace schema: every event kind the control plane can emit, with the
#: payload keys it carries.  ``emit`` rejects unknown kinds so the trace
#: stays machine-readable (the whole point over ``Telemetry.event``).
TRACE_KINDS: Dict[str, str] = {
    "run_start": "engine run begins (ticks, dt, level)",
    "run_end": "engine run ends (completed, dropped, swaps)",
    "dfs_commit": "DFS actuator committed new island rates (version, rates)",
    "dfs_guard": "DFS guard discarded a requested move (islands, requested)",
    "dfs_clamp": "DFS request clamped to the tech node's legal DVFS "
                 "range (islands, requested)",
    "lb_split": "LoadBalancer split decision snapshot (mode, weights)",
    "slo_drop_start": "SLO deadline drops began (tiles)",
    "slo_drop_end": "SLO deadline drop span ended (ticks, dropped)",
    "fault_kill": "tile(s) killed (tiles)",
    "fault_revive": "tile(s) revived (tiles)",
    "fault_link_degrade": "link bandwidth degraded (a, b, scale)",
    "fault_link_restore": "link bandwidth restored (a, b)",
    "fault_stuck": "island actuator stuck at a hardware rate (island, rate)",
    "fault_unstuck": "island actuator released (island)",
    "detected_dead": "online detector believes tile(s) dead (tiles)",
    "detected_alive": "online detector believes tile(s) recovered (tiles)",
    "straggler_suspect": "online detector flags straggler tile(s) (tiles)",
}


@dataclass(frozen=True)
class TraceEvent:
    """One schema'd control-plane event: monotonic tick, registered kind,
    a short human subject (tile/island/link names), structured payload."""
    tick: int
    kind: str
    subject: str = ""
    data: Dict[str, object] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, object]:
        return {"tick": self.tick, "kind": self.kind,
                "subject": self.subject, "data": _json_safe(self.data)}

    @classmethod
    def from_dict(cls, d: Mapping[str, object]) -> "TraceEvent":
        return cls(tick=int(d["tick"]), kind=str(d["kind"]),
                   subject=str(d.get("subject", "")),
                   data=dict(d.get("data", {})))


def _subject_of(kind: str, payload: Mapping[str, object]) -> str:
    """Derive a stable, human-readable subject from a payload dict."""
    if "tiles" in payload:
        tiles = payload["tiles"]
        if isinstance(tiles, (list, tuple)):
            return ",".join(str(t) for t in tiles)
        return str(tiles)
    if "island" in payload:
        return str(payload["island"])
    if "a" in payload and "b" in payload:
        return f"{payload['a']}-{payload['b']}"
    if "domain" in payload:
        return str(payload["domain"])
    return ""


class ControlTrace:
    """Ring-bounded store of :class:`TraceEvent` with JSONL export.

    Enforces the schema (``kind`` must be registered in :data:`TRACE_KINDS`)
    and monotonic tick stamps; bounded by ``capacity`` like every other
    long-soak store in the repo (oldest events fall off first).
    """

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._events: Deque[TraceEvent] = deque(maxlen=self.capacity)
        self._last_tick = -1
        self.total_emitted = 0

    def __len__(self) -> int:
        return len(self._events)

    def emit(self, tick: int, kind: str, subject: str = "",
             **data: object) -> TraceEvent:
        if kind not in TRACE_KINDS:
            raise ValueError(
                f"unknown trace kind {kind!r}; registered kinds: "
                f"{sorted(TRACE_KINDS)}")
        tick = int(tick)
        if tick < self._last_tick:
            raise ValueError(
                f"non-monotonic trace tick {tick} after {self._last_tick}")
        self._last_tick = tick
        if not subject:
            subject = _subject_of(kind, data)
        ev = TraceEvent(tick=tick, kind=kind, subject=subject,
                        data=_json_safe(data))
        self._events.append(ev)
        self.total_emitted += 1
        return ev

    def events(self, kind: Optional[str] = None) -> List[TraceEvent]:
        if kind is None:
            return list(self._events)
        return [e for e in self._events if e.kind == kind]

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self._events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out

    def spans(self, start_kind: str, end_kind: str) -> List[Tuple[int, int]]:
        """(start_tick, end_tick) pairs for edge-triggered span events."""
        out: List[Tuple[int, int]] = []
        open_tick: Optional[int] = None
        for e in self._events:
            if e.kind == start_kind and open_tick is None:
                open_tick = e.tick
            elif e.kind == end_kind and open_tick is not None:
                out.append((open_tick, e.tick))
                open_tick = None
        return out

    # -- JSONL round trip ------------------------------------------------
    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(e.to_dict()) for e in self._events) + (
            "\n" if self._events else "")

    @classmethod
    def from_jsonl(cls, text: str, capacity: int = 4096) -> "ControlTrace":
        tr = cls(capacity=capacity)
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            ev = TraceEvent.from_dict(d)
            tr._events.append(ev)
            tr._last_tick = max(tr._last_tick, ev.tick)
            tr.total_emitted += 1
        return tr


# ---------------------------------------------------------------------------
# Hardware-counter plane
# ---------------------------------------------------------------------------

TILE_KINDS = ("offered", "invocations", "busy_ticks", "stall_ticks",
              "cap_sum", "hop_flits", "slowdown_sum")
LINK_KINDS = ("flits", "util_sum", "peak_util")
ISLAND_KINDS = ("energy_j",)
STALL_EPS = 1e-9    # queue threshold distinguishing exact-0 from cumsum dust


class CounterPlane:
    """The hardware-counter plane: per-tile / per-link / per-island
    accumulators with optional leading batch axes.

    Per-tile (``lead + (A,)``):

    - ``offered``       — Σ admitted requests
    - ``invocations``   — Σ served requests (accelerator invocations)
    - ``busy_ticks``    — Σ busy fraction (tick-integral of utilization)
    - ``stall_ticks``   — Σ 1[queue backlog after the tick > ε]
    - ``cap_sum``       — Σ per-tick capacity (nominal work the tile could
      have served; ``invocations / cap_sum`` is effective vs. nominal rate)
    - ``hop_flits``     — Σ served · pkts/req · hop count (hop-weighted
      traffic the tile's stream put on the fabric)
    - ``slowdown_sum``  — Σ (contention slowdown − 1) (exposure integral)

    Per-link (``lead + (L,)``):

    - ``flits``     — Σ offered link load / flit size
    - ``util_sum``  — Σ per-tick link utilization (load / f_noc-scaled bw)
    - ``peak_util`` — max-latched per-tick link utilization

    Per-island (``lead + (I,)``): ``energy_j`` — the energy integral, NoC
    share booked to the ``noc_mem`` island.

    :meth:`reset` mirrors ``core/monitor.py:manual_reset`` scoping —
    ``kinds=`` selects which counters clear (default: all), ``tiles=``
    restricts tile-kind clears to named/indexed tiles.
    """

    def __init__(self, n_tiles: int, n_links: int, n_islands: int, *,
                 lead: Tuple[int, ...] = (),
                 tile_names: Sequence[str] = (),
                 island_names: Sequence[str] = ()):
        self.n_tiles = int(n_tiles)
        self.n_links = int(n_links)
        self.n_islands = int(n_islands)
        self.lead = tuple(int(x) for x in lead)
        self.tile_names = tuple(tile_names)
        self.island_names = tuple(island_names)
        self.tile = {k: np.zeros(self.lead + (self.n_tiles,))
                     for k in TILE_KINDS}
        self.link = {k: np.zeros(self.lead + (self.n_links,))
                     for k in LINK_KINDS}
        self.island = {k: np.zeros(self.lead + (self.n_islands,))
                       for k in ISLAND_KINDS}
        self.ticks = np.zeros(self.lead)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_arrays(cls, *, tile: Mapping[str, np.ndarray],
                    link: Mapping[str, np.ndarray],
                    island: Mapping[str, np.ndarray],
                    ticks, lead: Tuple[int, ...] = (),
                    tile_names: Sequence[str] = (),
                    island_names: Sequence[str] = ()) -> "CounterPlane":
        """Build a plane from already-accumulated arrays (the jax backend
        hands its scan-carry accumulators over through this)."""
        any_tile = next(iter(tile.values()))
        any_link = next(iter(link.values())) if link else np.zeros(lead + (0,))
        any_isl = next(iter(island.values())) if island else np.zeros(lead + (0,))
        cp = cls(any_tile.shape[-1], any_link.shape[-1], any_isl.shape[-1],
                 lead=lead, tile_names=tile_names, island_names=island_names)
        for k in TILE_KINDS:
            if k in tile:
                cp.tile[k] = np.asarray(tile[k], dtype=np.float64)
        for k in LINK_KINDS:
            if k in link:
                cp.link[k] = np.asarray(link[k], dtype=np.float64)
        for k in ISLAND_KINDS:
            if k in island:
                cp.island[k] = np.asarray(island[k], dtype=np.float64)
        cp.ticks = np.asarray(ticks, dtype=np.float64)
        return cp

    # -- windowing -------------------------------------------------------
    def reset(self, kinds: Optional[Sequence[str]] = None,
              tiles: Optional[Sequence] = None) -> None:
        """Clear counters, ``manual_reset``-style.

        ``kinds`` — counter names to clear (default: every counter);
        ``tiles`` — restrict *tile-kind* clears to these tiles (names or
        indices); link/island kinds ignore the tile scope, as the monitor's
        per-tile scoping did for its per-tile counters.
        """
        if kinds is None:
            kinds = TILE_KINDS + LINK_KINDS + ISLAND_KINDS + ("ticks",)
        unknown = [k for k in kinds
                   if k not in TILE_KINDS + LINK_KINDS + ISLAND_KINDS
                   and k != "ticks"]
        if unknown:
            raise ValueError(f"unknown counter kinds {unknown}")
        idx = None
        if tiles is not None:
            idx = [self.tile_names.index(t) if isinstance(t, str) else int(t)
                   for t in tiles]
        for k in kinds:
            if k in TILE_KINDS:
                if idx is None:
                    self.tile[k][...] = 0.0
                else:
                    self.tile[k][..., idx] = 0.0
            elif k in LINK_KINDS:
                self.link[k][...] = 0.0
            elif k in ISLAND_KINDS:
                self.island[k][...] = 0.0
            elif k == "ticks" and idx is None:
                self.ticks = np.zeros(self.lead)

    # -- views -----------------------------------------------------------
    def design(self, b: int) -> "CounterPlane":
        """One design's scalar-lead view of a batched plane (copies)."""
        if not self.lead:
            raise ValueError("design() needs a batched (lead-axis) plane")
        cp = CounterPlane(self.n_tiles, self.n_links, self.n_islands,
                          lead=self.lead[1:], tile_names=self.tile_names,
                          island_names=self.island_names)
        for k in TILE_KINDS:
            cp.tile[k] = self.tile[k][b].copy()
        for k in LINK_KINDS:
            cp.link[k] = self.link[k][b].copy()
        for k in ISLAND_KINDS:
            cp.island[k] = self.island[k][b].copy()
        cp.ticks = np.asarray(self.ticks)[b].copy()
        return cp

    def snapshot(self) -> Dict[str, object]:
        return {
            "ticks": np.asarray(self.ticks).copy(),
            "tile": {k: v.copy() for k, v in self.tile.items()},
            "link": {k: v.copy() for k, v in self.link.items()},
            "island": {k: v.copy() for k, v in self.island.items()},
            "tile_names": self.tile_names,
            "island_names": self.island_names,
        }

    # -- derived rates ---------------------------------------------------
    def _per_tick(self, x: np.ndarray) -> np.ndarray:
        t = np.maximum(np.asarray(self.ticks, dtype=np.float64), 1.0)
        return x / t[..., None] if x.ndim > np.ndim(t) else x / t

    def effective_rate(self) -> np.ndarray:
        """Served / nominal-capacity per tile — the paper's effective vs.
        nominal accelerator rate."""
        cap = self.tile["cap_sum"]
        return np.where(cap > 0.0, self.tile["invocations"]
                        / np.where(cap > 0.0, cap, 1.0), 0.0)

    def mean_busy(self) -> np.ndarray:
        return self._per_tick(self.tile["busy_ticks"])

    def stall_frac(self) -> np.ndarray:
        return self._per_tick(self.tile["stall_ticks"])

    def mean_slowdown(self) -> np.ndarray:
        return 1.0 + self._per_tick(self.tile["slowdown_sum"])

    def link_utilization(self) -> np.ndarray:
        return self._per_tick(self.link["util_sum"])

    def summary(self) -> Dict[str, float]:
        """Scalar roll-up (per-design when lead axes are present this
        reduces over them too) — what ``closed_loop_score`` attaches to
        each survivor."""
        inv = self.tile["invocations"]
        return {
            "ticks": float(np.asarray(self.ticks).max(initial=0.0)),
            "offered": float(self.tile["offered"].sum()),
            "invocations": float(inv.sum()),
            "busy_frac": float(self.mean_busy().mean()) if inv.size else 0.0,
            "stall_frac": float(self.stall_frac().mean()) if inv.size else 0.0,
            "effective_rate": float(self.effective_rate().mean())
            if inv.size else 0.0,
            "hop_flits": float(self.tile["hop_flits"].sum()),
            "mean_slowdown": float(self.mean_slowdown().mean())
            if inv.size else 1.0,
            "link_flits": float(self.link["flits"].sum()),
            "peak_link_util": float(self.link["peak_util"].max(initial=0.0)),
            "mean_link_util": float(self.link_utilization().mean())
            if self.link["util_sum"].size else 0.0,
            "energy_j": float(self.island["energy_j"].sum()),
        }

    def allclose(self, other: "CounterPlane", *, rtol: float = 1e-9,
                 atol: float = 1e-9) -> bool:
        for mine, theirs in ((self.tile, other.tile),
                             (self.link, other.link),
                             (self.island, other.island)):
            for k in mine:
                if not np.allclose(mine[k], theirs[k], rtol=rtol, atol=atol):
                    return False
        return bool(np.allclose(self.ticks, other.ticks,
                                rtol=rtol, atol=atol))


# ---------------------------------------------------------------------------
# Capture strategies
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CaptureContext:
    """Everything a capture needs from the engine, read-only: the
    ``StepConsts`` digest plus the tile->island map."""
    base_mbps: np.ndarray
    req_mb: np.ndarray
    hop_counts: np.ndarray
    link_bw: float
    noc_power_share: float
    dt: float
    island_of_tile: np.ndarray      # (A,) -> island index
    noc_island: int
    n_links: int
    n_islands: int
    dynamic_contention: bool = True
    own_demand: Optional[np.ndarray] = None     # (..., A) flow MB/s
    inc: Optional[np.ndarray] = None            # (..., A, L) incidence

    @classmethod
    def from_consts(cls, consts, *, island_of_tile: np.ndarray,
                    noc_island: int, n_links: int,
                    n_islands: int) -> "CaptureContext":
        return cls(base_mbps=np.asarray(consts.base_mbps, float),
                   req_mb=np.asarray(consts.req_mb, float),
                   hop_counts=np.asarray(consts.hop_counts, float),
                   link_bw=float(consts.link_bw),
                   noc_power_share=float(consts.noc_power_share),
                   dt=float(consts.dt),
                   island_of_tile=np.asarray(island_of_tile, np.int64),
                   noc_island=int(noc_island), n_links=int(n_links),
                   n_islands=int(n_islands),
                   dynamic_contention=bool(consts.dynamic_contention),
                   own_demand=(None if consts.own_demand is None
                               else np.asarray(consts.own_demand, float)),
                   inc=(None if consts.inc is None
                        else np.asarray(consts.inc, float)))

    def island_onehot(self) -> np.ndarray:
        """(A, I) membership used to scatter per-tile power to islands."""
        A = self.island_of_tile.shape[0]
        oh = np.zeros((A, self.n_islands))
        oh[np.arange(A), self.island_of_tile] = 1.0
        return oh


class DeferredCapture:
    """Deferred capture for the Python tick loops (sequential engine and
    the batched NumPy engine, via ``lead=(B,)``): the per-tick hot path
    is ONE store of the ``dyn`` row — a reference append for the
    sequential loop, a preallocated slot copy for the batched one — plus
    piecewise-constant service segments recorded at each recompute.
    Everything else, the link loads included, is reconstructed
    vectorized at :meth:`finalize` from the histories the engine already
    keeps: the wire load at tick ``t`` is a pure function of the
    *previous* tick's busy fractions (``tick_step`` contracts
    ``own_demand * busy`` over the incidence before updating ``busy``),
    and busy itself replays exactly as ``served / cap``."""

    def __init__(self, ctx: CaptureContext, T: int, *,
                 lead: Tuple[int, ...] = (),
                 tile_alive: Optional[np.ndarray] = None,
                 link_scale: Optional[np.ndarray] = None,
                 tile_names: Sequence[str] = (),
                 island_names: Sequence[str] = ()):
        self.ctx = ctx
        self.T = int(T)
        self.lead = tuple(int(x) for x in lead)
        A = ctx.base_mbps.shape[-1]
        # batched runs copy each (B, A) dyn row into a preallocated
        # history (keeping B-wide rows alive would defeat the allocator's
        # buffer recycling); the sequential loop's rows are a few dozen
        # bytes, so a plain reference append is both safe and ~10x
        # cheaper than a numpy slot write there
        if self.lead:
            self._dyn_buf: Optional[np.ndarray] = np.empty(
                (self.T,) + self.lead + (A,))
            self._dyn_list: Optional[List[np.ndarray]] = None
        else:
            self._dyn_buf = None
            self._dyn_list = []
        self._segments: List[Tuple[int, Dict[str, np.ndarray]]] = []
        self._tile_alive = tile_alive            # (T, A) or None
        self._link_scale = link_scale            # (T, L) or None
        self.tile_names = tuple(tile_names)
        self.island_names = tuple(island_names)
        self.plane: Optional[CounterPlane] = None

    # hot path -----------------------------------------------------------
    def on_service(self, start_tick: int, svc: Mapping[str, object]) -> None:
        """Record a service-term segment starting at ``start_tick``
        (run start, stuck-actuator apply, or the tick after a commit)."""
        self._segments.append((int(start_tick), {
            "t_comp": np.array(svc["t_comp"], dtype=np.float64, copy=True),
            "t_wire": np.array(svc["t_wire"], dtype=np.float64, copy=True),
            "t_ref": np.array(svc["t_ref"], dtype=np.float64, copy=True),
            "f_tile": np.array(svc["f_tile"], dtype=np.float64, copy=True),
            "f_noc": np.array(svc["f_noc"], dtype=np.float64, copy=True),
        }))

    def on_tick(self, t_i: int, out) -> None:
        if self._dyn_list is not None:
            self._dyn_list.append(out.dyn)
        else:
            self._dyn_buf[t_i] = out.dyn

    # reconstruction -----------------------------------------------------
    def finalize(self, admitted: np.ndarray, served: np.ndarray,
                 queue_drops: Optional[np.ndarray] = None) -> CounterPlane:
        """Rebuild the full counter plane from ``(T,) + lead + (A,)``
        histories + the captured dyn/load rows.  Capacity is recomputed
        segment-by-segment with the *identical* float expression
        ``tick_step`` used, so ``busy = served / cap`` reconstructs the
        exact per-tick busy fractions the engine produced."""
        ctx, T, lead = self.ctx, self.T, self.lead
        A = ctx.base_mbps.shape[-1]
        cp = CounterPlane(A, ctx.n_links, ctx.n_islands, lead=lead,
                          tile_names=self.tile_names,
                          island_names=self.island_names)
        if T == 0:
            self.plane = cp
            return cp
        segs = sorted(self._segments, key=lambda s: s[0])
        assert segs and segs[0][0] == 0, "on_service(0, svc) never recorded"
        bounds = [s[0] for s in segs] + [T]

        dyn_all = (self._dyn_buf if self._dyn_buf is not None
                   else np.stack(self._dyn_list))

        cap = np.empty((T,) + lead + (A,))
        f_tile = np.empty((T,) + lead + (A,))
        f_noc = np.empty((T,) + lead)
        for (s, svc), e in zip(segs, bounds[1:]):
            if e <= s:
                continue
            dyn = dyn_all[s:e]
            # identical op order to tick_step's cap_tick expression
            cap[s:e] = (ctx.base_mbps * svc["t_ref"]
                        / (svc["t_comp"] + svc["t_wire"] * dyn)
                        / ctx.req_mb) * ctx.dt
            f_tile[s:e] = svc["f_tile"]
            f_noc[s:e] = svc["f_noc"]

        alive = self._tile_alive
        if alive is not None and lead:
            # the shared (T, A) fault mask broadcast against lead axes
            alive = np.asarray(alive)[
                (slice(None),) + (None,) * len(lead) + (slice(None),)]
        if alive is None:
            cap_eff = cap
            busy = served / cap
        else:
            cap_eff = cap * alive[:T]
            busy = np.where(cap_eff > 0.0,
                            served / np.where(cap_eff > 0.0, cap_eff, 1.0),
                            0.0)

        # queue after each tick (per tile): cumulative admitted − exits.
        exits = served if queue_drops is None else served + queue_drops
        queue_after = np.cumsum(admitted - exits, axis=0)

        pkt = ctx.req_mb * 1e6 / PKT_BYTES
        cp.tile["offered"] = admitted.sum(axis=0)
        cp.tile["invocations"] = served.sum(axis=0)
        cp.tile["busy_ticks"] = busy.sum(axis=0)
        cp.tile["stall_ticks"] = (queue_after > STALL_EPS).sum(axis=0).astype(float)
        cp.tile["cap_sum"] = cap_eff.sum(axis=0)
        cp.tile["hop_flits"] = (served * pkt * ctx.hop_counts).sum(axis=0)
        cp.tile["slowdown_sum"] = (dyn_all - 1.0).sum(axis=0)

        if ctx.dynamic_contention and ctx.own_demand is not None \
                and ctx.inc is not None:
            # replay the wire loads with tick_step's own contraction: the
            # load at tick t is driven by the busy fractions of tick t-1
            # (busy starts the run at zero), then per-segment reductions
            # divide by the piecewise-constant NoC frequency AFTER the
            # tickwise sum/max — division by a positive constant is
            # monotonic, so the maximum commutes with it
            busy_prev = np.concatenate(
                [np.zeros((1,) + lead + (A,)), busy[:-1]], axis=0)
            loads = np.einsum("...a,...al->...l",
                              ctx.own_demand * busy_prev, ctx.inc)
            if self._link_scale is not None:
                lscale = np.asarray(self._link_scale)[:T]
                if lead:
                    lscale = lscale[(slice(None),) + (None,) * len(lead)
                                    + (slice(None),)]
                loads = loads / lscale
            flit_sum = np.zeros(lead + (ctx.n_links,))
            util_sum = np.zeros(lead + (ctx.n_links,))
            peak = np.zeros(lead + (ctx.n_links,))
            for (s, svc), e in zip(segs, bounds[1:]):
                if e <= s:
                    continue
                seg_sum = loads[s:e].sum(axis=0)
                seg_max = loads[s:e].max(axis=0, initial=0.0)
                denom = ctx.link_bw * svc["f_noc"][..., None]
                flit_sum += seg_sum
                util_sum += seg_sum / denom
                np.maximum(peak, seg_max / denom, out=peak)
            cp.link["flits"] = flit_sum / PKT_BYTES
            cp.link["util_sum"] = util_sum
            cp.link["peak_util"] = peak

        power = chip_power(f_tile, busy)
        if alive is not None:
            power = power * alive[:T]
        onehot = ctx.island_onehot()
        energy = (power.sum(axis=0) * ctx.dt) @ onehot
        if ctx.noc_island >= 0:
            noc_energy = (ctx.noc_power_share
                          * chip_power(f_noc, 1.0)).sum(axis=0) * ctx.dt
            energy[..., ctx.noc_island] += noc_energy
        cp.island["energy_j"] = energy
        cp.ticks = np.full(lead, float(T))
        self.plane = cp
        return cp


class IncrementalCapture:
    """Batched-NumPy capture: straight per-tick accumulation into a
    ``lead=(B,)`` plane.  The adds are O(B·(A+L)) elementwise work per
    tick — small next to the engine's (B, A, L) link contraction — and
    keep memory bounded at large B (no (T, B, L) buffers)."""

    def __init__(self, ctx: CaptureContext, *, lead: Tuple[int, ...],
                 tile_names: Sequence[str] = (),
                 island_names: Sequence[str] = ()):
        self.ctx = ctx
        A = ctx.base_mbps.shape[-1]
        self.plane = CounterPlane(A, ctx.n_links, ctx.n_islands, lead=lead,
                                  tile_names=tile_names,
                                  island_names=island_names)
        self._onehot = ctx.island_onehot()
        self._pkt = ctx.req_mb * 1e6 / PKT_BYTES

    def on_tick(self, out, *, queue: np.ndarray, busy: np.ndarray,
                svc: Mapping[str, object],
                alive: Optional[np.ndarray] = None) -> None:
        ctx, cp = self.ctx, self.plane
        t = cp.tile
        t["offered"] += out.admitted
        t["invocations"] += out.served
        t["busy_ticks"] += busy
        t["stall_ticks"] += (queue > STALL_EPS)
        t["cap_sum"] += out.cap_tick
        t["hop_flits"] += out.served * self._pkt * ctx.hop_counts
        t["slowdown_sum"] += out.dyn - 1.0
        if ctx.dynamic_contention and out.link_loads is not None:
            f_noc = np.asarray(svc["f_noc"], dtype=np.float64)
            util = out.link_loads / (ctx.link_bw * f_noc[..., None])
            ln = cp.link
            ln["flits"] += out.link_loads / PKT_BYTES
            ln["util_sum"] += util
            np.maximum(ln["peak_util"], util, out=ln["peak_util"])
        power = chip_power(np.asarray(svc["f_tile"], dtype=np.float64), busy)
        if alive is not None:
            power = power * alive
        cp.island["energy_j"] += (power @ self._onehot) * ctx.dt
        if ctx.noc_island >= 0:
            noc_p = ctx.noc_power_share * chip_power(
                np.asarray(svc["f_noc"], dtype=np.float64), 1.0)
            cp.island["energy_j"][..., ctx.noc_island] += noc_p * ctx.dt
        cp.ticks = cp.ticks + 1.0


# ---------------------------------------------------------------------------
# Profiler: phase totals, a ring of spans, counters
# ---------------------------------------------------------------------------

#: Spans and counter events the ring keeps.  A whole sweep records some 60
#: spans and a co-sim ranking under 10, so a 40 s window with its warm-up
#: fills a few per cent of it.
RING_CAPACITY = 1 << 14


class Span(NamedTuple):
    """One closed span, or one counter event, of a :class:`Profiler`'s
    ring.  ``start_ns``/``end_ns`` are ``time.time_ns()`` stamps: the
    clock of the JAX profiler's host events, so a ring and a device trace
    of one run line up.  A counter event has ``start_ns == end_ns`` and
    its increment in ``count``."""
    seq: int                    # open order, unique within one profiler
    name: str
    parent: Optional[int]       # seq of the enclosing span; None at a root
    start_ns: int
    end_ns: int
    count: Optional[int] = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def _annotate(name: str):
    """An open ``jax.profiler.TraceAnnotation`` named ``name`` (it lands in
    the ``/host:CPU`` plane of a traced run), or None where JAX is not
    imported: the recorder never imports it."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    ann = profiler.TraceAnnotation(name)
    ann.__enter__()
    return ann


class Profiler:
    """Span and counter recorder: ``with prof.profile("sweep_chunk"):``
    around a code region books its elapsed time under that phase name
    (:meth:`summary`) and keeps the span — name, start, end, enclosing
    span — in a bounded ring (:meth:`spans`); :meth:`count` books a
    running total and a counter event under the current span.

    :meth:`reset` clears the totals only: the ring is a recent history,
    whose oldest entries fall off first and are counted in ``dropped``.
    The enclosing span is tracked per thread."""

    def __init__(self) -> None:
        self.phases: Dict[str, List[float]] = {}   # name -> [total_s, count]
        self.counts: Dict[str, int] = {}
        self._ring: Deque[Span] = deque(maxlen=RING_CAPACITY)
        self.dropped = 0
        self._seq = itertools.count()
        self._local = threading.local()
        self._gc_open: Optional[tuple] = None

    def record(self, name: str, seconds: float) -> None:
        slot = self.phases.setdefault(name, [0.0, 0])
        slot[0] += float(seconds)
        slot[1] += 1

    def profile(self, name: str) -> "_SpanTimer":
        return _SpanTimer(self, name)

    def count(self, name: str, n: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + n
        now = time.time_ns()
        self._keep(Span(next(self._seq), name, self._current(), now, now,
                        n))

    def spans(self) -> List[Span]:
        """The ring, oldest first (spans in the order they closed)."""
        return list(self._ring)

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {name: {"total_s": total, "count": count,
                       "mean_s": total / count if count else 0.0}
                for name, (total, count) in sorted(self.phases.items())}

    def reset(self) -> None:
        self.phases.clear()
        self.counts.clear()

    # -- internals -------------------------------------------------------
    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _current(self) -> Optional[int]:
        stack = self._stack()
        return stack[-1] if stack else None

    def _keep(self, span: Span) -> None:
        if len(self._ring) == RING_CAPACITY:
            self.dropped += 1
        self._ring.append(span)

    def _on_gc(self, phase: str, info: Mapping[str, object]) -> None:
        """``gc.callbacks`` hook: each full (generation-2) collection
        becomes a ``gc_full`` span under the span it interrupted."""
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_open = (next(self._seq), self._current(),
                             _annotate("gc_full"), time.time_ns())
        elif self._gc_open is not None:
            seq, parent, ann, start = self._gc_open
            self._gc_open = None
            end = time.time_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.record("gc_full", (end - start) * 1e-9)
            self._keep(Span(seq, "gc_full", parent, start, end))


class _SpanTimer(ContextDecorator):
    """One span of a :class:`Profiler`.  ``seconds`` is its duration once
    closed; :meth:`close` ends it before the ``with`` block does."""

    def __init__(self, profiler: Profiler, name: str):
        self.profiler = profiler
        self.name = name
        self.seq = -1
        self.parent: Optional[int] = None
        self.start_ns = self.end_ns = 0
        self._ann = None

    def _recreate_cm(self) -> "_SpanTimer":
        # a decorated function opens a span of its own on every call
        return _SpanTimer(self.profiler, self.name)

    def __enter__(self) -> "_SpanTimer":
        prof = self.profiler
        stack = prof._stack()
        self.parent = stack[-1] if stack else None
        self.seq = next(prof._seq)
        stack.append(self.seq)
        self._ann = _annotate(self.name)
        self.end_ns = 0
        self.start_ns = time.time_ns()
        return self

    def close(self) -> None:
        if self.end_ns:
            return
        self.end_ns = time.time_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        prof = self.profiler
        stack = prof._stack()
        if self.seq in stack:
            stack.remove(self.seq)
        prof.record(self.name, self.seconds)
        prof._keep(Span(self.seq, self.name, self.parent, self.start_ns,
                        self.end_ns))

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


_GLOBAL_PROFILER = Profiler()
gc.callbacks.append(_GLOBAL_PROFILER._on_gc)


def get_profiler() -> Profiler:
    """The process-global recorder (what :func:`profiled` books to when
    no explicit profiler is given)."""
    return _GLOBAL_PROFILER


def reset_profiler() -> None:
    _GLOBAL_PROFILER.reset()


def profiled(name: str, profiler: Optional[Profiler] = None) -> _SpanTimer:
    """Context manager / decorator recording a span into ``profiler`` (the
    global one by default)::

        with observe.profiled("sweep_chunk"):
            evaluate(chunk)
    """
    return _SpanTimer(profiler or _GLOBAL_PROFILER, name)


# ---------------------------------------------------------------------------
# Observer façade
# ---------------------------------------------------------------------------


class Observer:
    """Engine-facing monitoring façade with the ``level=`` knob.

    - ``"off"``       — no counters, no tracing (the engines skip every hook)
    - ``"counters"``  — hardware-counter plane only (the cheap mode the
      DSE loop runs at scale; also what the jax backend supports)
    - ``"full"``      — counters + control-plane tracing (+ SLO spans,
      balancer snapshots)

    One observer instance is bound to one engine; after a run,
    ``observer.counters`` holds the :class:`CounterPlane` and
    ``observer.trace`` the :class:`ControlTrace`.
    """

    def __init__(self, level: str = "counters", *,
                 trace_capacity: int = 4096):
        if level not in LEVELS:
            raise ValueError(f"level must be one of {LEVELS}, got {level!r}")
        self.level = level
        self.trace = ControlTrace(capacity=trace_capacity)
        self._counters: Optional[CounterPlane] = None
        self._counters_thunk = None

    @property
    def counters(self) -> Optional[CounterPlane]:
        """The last run's :class:`CounterPlane` — materialized lazily on
        first read.  The engines hand over a finalize thunk instead of a
        built plane (:meth:`attach_lazy`), so the hot tick loop never
        pays the vectorized reconstruction."""
        if self._counters is None and self._counters_thunk is not None:
            thunk, self._counters_thunk = self._counters_thunk, None
            self._counters = thunk()
        return self._counters

    # -- coercion --------------------------------------------------------
    @classmethod
    def coerce(cls, observe) -> Optional["Observer"]:
        """Normalize an engine's ``observe=`` argument: ``None``/``"off"``
        -> no observer; a level string -> fresh observer; an
        :class:`Observer` -> itself."""
        if observe is None or observe == "off":
            return None
        if isinstance(observe, Observer):
            return observe if observe.enabled else None
        if isinstance(observe, str):
            return cls(level=observe)
        raise TypeError(f"observe= expects None, a level string in {LEVELS},"
                        f" or an Observer; got {type(observe).__name__}")

    @property
    def enabled(self) -> bool:
        return self.level != "off"

    @property
    def tracing(self) -> bool:
        return self.level == "full"

    def begin_run(self) -> None:
        """Reset per-run state (engines call this at run start): each run
        gets a fresh trace — mirroring :meth:`attach`, which replaces the
        counter plane — so a reused observer never trips the trace's
        monotonic-tick guard on the next run's tick 0."""
        self.trace = ControlTrace(capacity=self.trace.capacity)

    # -- tracing ---------------------------------------------------------
    def emit(self, tick: int, kind: str, subject: str = "",
             **data: object) -> None:
        if self.tracing:
            self.trace.emit(tick, kind, subject, **data)

    def emit_event_dict(self, tick: int, ev: Mapping[str, object]) -> None:
        """Adapter for the compiled-fault / supervisor event dicts: maps
        their ``kind`` + payload onto the trace schema."""
        if not self.tracing:
            return
        kind = str(ev["kind"])
        if kind not in TRACE_KINDS:
            return                      # foreign event kinds stay in telemetry
        payload = {k: v for k, v in ev.items() if k not in ("tick", "kind")}
        self.trace.emit(tick, kind, **payload)

    # -- capture construction -------------------------------------------
    def capture_sequential(self, *, T: int, consts, island_of_tile,
                           noc_island: int, n_links: int, n_islands: int,
                           lead=(), tile_alive=None, link_scale=None,
                           tile_names=(), island_names=()
                           ) -> DeferredCapture:
        """Deferred capture for the Python tick loops — the sequential
        engine (``lead=()``) and the batched NumPy engine
        (``lead=(B,)``); both pay one slot-write per tick."""
        ctx = CaptureContext.from_consts(
            consts, island_of_tile=island_of_tile, noc_island=noc_island,
            n_links=n_links, n_islands=n_islands)
        return DeferredCapture(ctx, T, lead=tuple(lead),
                               tile_alive=tile_alive,
                               link_scale=link_scale,
                               tile_names=tile_names,
                               island_names=island_names)

    def capture_incremental(self, *, lead, consts, island_of_tile,
                            noc_island: int, n_links: int, n_islands: int,
                            tile_names=(), island_names=()
                            ) -> IncrementalCapture:
        ctx = CaptureContext.from_consts(
            consts, island_of_tile=island_of_tile, noc_island=noc_island,
            n_links=n_links, n_islands=n_islands)
        return IncrementalCapture(ctx, lead=tuple(lead),
                                  tile_names=tile_names,
                                  island_names=island_names)

    def attach(self, plane: CounterPlane) -> CounterPlane:
        """Install a finished counter plane (accumulating across runs is
        the caller's concern; each run replaces the plane)."""
        self._counters = plane
        self._counters_thunk = None
        return plane

    def attach_lazy(self, thunk) -> None:
        """Install a zero-argument callable producing the run's
        :class:`CounterPlane`; it is invoked (once) on the first
        ``observer.counters`` read.  The captured histories are the
        engine's own run buffers — freshly allocated each run — so the
        thunk stays valid until the next run replaces it."""
        self._counters = None
        self._counters_thunk = thunk


# ---------------------------------------------------------------------------
# Metrics-export bridge
# ---------------------------------------------------------------------------


def export_metrics(*, telemetry=None, counters: Optional[CounterPlane] = None,
                   trace: Optional[ControlTrace] = None,
                   registry=None, prefix: str = "sim"):
    """Render telemetry + the counter plane + the trace into a
    :class:`~repro.sim.metrics.MetricsRegistry` (Prometheus-ready).

    Counter-plane series carry ``tile=`` / ``link=`` / ``island=`` labels;
    telemetry scalars become gauges of their latest row; trace kinds
    become an event counter."""
    from repro.sim.metrics import MetricsRegistry
    reg = registry if registry is not None else MetricsRegistry()

    if counters is not None:
        cp = counters
        tnames = (cp.tile_names if len(cp.tile_names) == cp.n_tiles
                  else tuple(str(i) for i in range(cp.n_tiles)))
        inames = (cp.island_names if len(cp.island_names) == cp.n_islands
                  else tuple(str(i) for i in range(cp.n_islands)))
        for k in TILE_KINDS:
            arr = np.asarray(cp.tile[k], dtype=np.float64)
            flat = arr.reshape(-1, cp.n_tiles).sum(axis=0)
            for a, name in enumerate(tnames):
                reg.counter(f"{prefix}_tile_{k}_total",
                            f"counter plane: per-tile {k}",
                            labels={"tile": name}, value=float(flat[a]))
        link_arr = np.asarray(cp.link["flits"], dtype=np.float64)
        for k in LINK_KINDS:
            arr = np.asarray(cp.link[k], dtype=np.float64)
            flat = (arr.reshape(-1, cp.n_links).max(axis=0)
                    if k == "peak_util"
                    else arr.reshape(-1, cp.n_links).sum(axis=0))
            metric = (reg.gauge if k == "peak_util" else reg.counter)
            for l in range(cp.n_links):
                metric(f"{prefix}_link_{k}" +
                       ("" if k == "peak_util" else "_total"),
                       f"counter plane: per-link {k}",
                       labels={"link": str(l)}, value=float(flat[l]))
        for k in ISLAND_KINDS:
            arr = np.asarray(cp.island[k], dtype=np.float64)
            flat = arr.reshape(-1, cp.n_islands).sum(axis=0)
            for i, name in enumerate(inames):
                reg.counter(f"{prefix}_island_{k}_total",
                            f"counter plane: per-island {k}",
                            labels={"island": name}, value=float(flat[i]))
        reg.gauge(f"{prefix}_observed_ticks",
                  "ticks accumulated into the counter plane",
                  value=float(np.asarray(cp.ticks).max(initial=0.0)))

    if telemetry is not None:
        doc = telemetry.to_dict()
        for name, series in doc.get("scalars", {}).items():
            if series:
                reg.gauge(f"{prefix}_telemetry_{name}",
                          f"latest telemetry {name}",
                          value=float(series[-1]))

    if trace is not None:
        for kind, n in sorted(trace.counts().items()):
            reg.counter(f"{prefix}_trace_events_total",
                        "control-plane trace events by kind",
                        labels={"kind": kind}, value=float(n))

    return reg
