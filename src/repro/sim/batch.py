"""Batched multi-design closed-loop co-simulation: B SoCs as one array
program.

``core/dse.py:grid_sweep`` evaluates millions of *static* design points
per second, but runtime validation (``closed_loop_score``) used to
re-simulate Pareto survivors one at a time — the static sweep scaled, the
closed loop didn't.  This module stacks B concrete designs (replication
counts, placements, island rates) into one platform whose tick loop
advances ``(B, A)`` arrays:

* per-tile queue/busy/counter state gains a leading design axis and is
  advanced by the SAME :func:`~repro.sim.engine.tick_step` the sequential
  engine runs — elementwise ops and trailing-axis reductions are
  shape-independent, so a B=1 batch run reproduces the sequential engine
  bit-for-bit (differential-tested);
* service rates come from ``service_time_terms_batch`` broadcast over the
  design axis (per-design ``f_acc``/``f_noc``/``f_tg``/K/placement);
* NoC contention uses per-design route->link incidence stacked into one
  dense ``(B, A, L)`` table (:func:`~repro.core.noc.stacked_incidence`:
  every route padded out to the full link-vector width, so per-tick link
  loads are a single einsum — the memory cost is ``B*A*L`` floats, fine
  for SoC-size fabrics);
* DFS controllers run vectorized: policy decisions on ``(B, I)`` counter
  windows, dual-buffer commits as masked array swaps
  (:class:`~repro.sim.control.BatchControllerHarness`);
* the workload may be a shared :class:`~repro.sim.traffic.Trace` or a
  per-design ``(T, B, A)`` :class:`~repro.sim.traffic.BatchTrace`
  (broadcasting a shared trace reproduces it bit-for-bit), shaped by an
  optional :class:`~repro.sim.flows.FlowPattern` (tile-to-tile streams,
  chained stages) with a :class:`~repro.sim.control.LoadBalancer`
  splitting arrivals across replica groups.

Three backends: ``"numpy"`` (float64, the ground-truth reference),
``"jax"`` — the tick loop as one ``jax.lax.scan`` (jit-compiled; float32
unless ``jax_enable_x64``), so the whole grid_sweep -> Pareto -> batched
co-sim pipeline can run jitted end to end — and ``"pallas"``, the
queue-update/service/forward tick sequence fused into one Pallas kernel
(:mod:`repro.kernels.tick_sim`; compiled on a TPU, interpreted on the
CPU).  The jax backend supports open-loop replay, the
vectorized membound/PID policies (+ queue guard), *custom* jax-side
batch policies (any policy exposing the ``jax_step`` protocol — see
:meth:`BatchSimEngine._control_plan`), flow patterns, per-design traces
and the balancer; it records no telemetry rings.  On both device
backends the latency percentiles are reconstructed on the device from the
histories the tick loop leaves there, certified equal to the host's
float64 reconstruction (:mod:`repro.sim.percentiles`).  With
``devices=`` the jax backend shards the design axis across devices via
``jax.shard_map`` (mesh plumbing in ``repro.shard``): the
per-design rows are fully independent, so any device count returns the
single-device floats exactly (differentially tested) — spin up virtual
CPU devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.islands import (IslandConfig, IslandSpec, NOC_LADDER,
                                TILE_LADDER)
from repro.core.noc import pos_index, positions_to_indices
from repro.core.perfmodel import SoCPerfModel
from repro.core.voltage import TechModel
from repro.sim.control import BatchControllerHarness, LoadBalancer
from repro.sim.engine import (PKT_BYTES, SimConfig, SimPlatform, StepConsts,
                              TickState, latency_percentiles, tick_step)
from repro.sim.faults import (CompiledFaults, FaultSchedule, SLOConfig,
                              compile_faults, respill_stranded)
from repro.sim.flows import FlowPattern, compile_flows
from repro.sim.observe import (STALL_EPS, CounterPlane, Observer,
                               get_profiler, profiled)
from repro.sim.telemetry import BatchTelemetry, TelemetrySchema
from repro.sim.traffic import BatchTrace, Trace

# every contraction of the jax backends runs at full f32 precision: a
# TPU's default rounds f32 matmul operands to bfloat16
HIGHEST = "highest"

# jitted-scan LRU bound: one compiled executable per distinct
# (trace shape, cadence, fault class, policy/balancer/config digest);
# long-lived engines swept through many configurations stay bounded
_SCAN_CACHE_MAX = 8

# slot names of the tuple ``BatchSimEngine._scan_cache_sig`` returns,
# in order.  A knob that retraces the scan must claim a slot (or join
# an existing digest slot); tests/test_analysis.py enumerates these and
# the RPR002 rule pass checks the construction stays complete.
SCAN_SIG_FIELDS = ("tag", "T", "ci", "dt", "B", "D", "arrivals_ndim",
                   "fault_key", "policy_digest", "balancer_digest",
                   "config", "model", "slo", "tech")


# ---------------------------------------------------------------------------
# Platform: B concrete designs, stacked
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BatchSimPlatform:
    """B simulatable SoC instances sharing one NoC/model and one island
    *structure* (names, tile partition, ladders); everything that varies
    across designs — replication, placement, island rates, TG rate — is a
    leading-``B``-axis array.  ``islands`` is the structural template; the
    live per-design rates live in ``rates`` (and evolve through a
    :class:`BatchControllerHarness` at run time).
    """
    model: SoCPerfModel
    islands: IslandConfig               # structure template (rates ignored)
    names: Tuple[str, ...]
    base_mbps: np.ndarray               # (B, A)
    wire_share: np.ndarray              # (B, A)
    k: np.ndarray                       # (B, A)
    pos_idx: np.ndarray                 # (B, A)
    req_mb: np.ndarray                  # (B, A)
    rates: np.ndarray                   # (B, I) initial island rates
    f_tg: np.ndarray                    # (B,)
    n_tg: int = 0
    flows: Optional[FlowPattern] = None  # shared tile-to-tile pattern

    @property
    def n_designs(self) -> int:
        return int(self.k.shape[0])

    @property
    def n_tiles(self) -> int:
        return len(self.names)

    @classmethod
    def stack(cls, platforms: Sequence[SimPlatform]) -> "BatchSimPlatform":
        """Stack B :class:`SimPlatform` instances (same model, tile names
        and island structure; per-design arrays may differ)."""
        assert platforms, "need at least one platform"
        p0 = platforms[0]
        isl_names = p0.islands.names()
        isl_tiles = tuple(i.tiles for i in p0.islands.islands)
        for p in platforms[1:]:
            assert p.model is p0.model or p.model == p0.model, \
                "platforms must share one SoCPerfModel"
            assert p.names == p0.names, "tile name mismatch"
            assert p.islands.names() == isl_names, "island structure mismatch"
            assert tuple(i.tiles for i in p.islands.islands) == isl_tiles
            assert p.n_tg == p0.n_tg, "n_tg mismatch"
            assert p.flows == p0.flows, "flow-pattern mismatch"
        return cls(
            flows=p0.flows,
            model=p0.model, islands=p0.islands, names=p0.names,
            base_mbps=np.stack([p.base_mbps for p in platforms]),
            wire_share=np.stack([p.wire_share for p in platforms]),
            k=np.stack([p.k for p in platforms]),
            pos_idx=np.stack([p.pos_idx for p in platforms]),
            req_mb=np.stack([p.req_mb for p in platforms]),
            rates=np.asarray([[i.rate for i in p.islands.islands]
                              for p in platforms], dtype=np.float64),
            f_tg=np.asarray([p.f_tg for p in platforms], dtype=np.float64),
            n_tg=p0.n_tg)

    @classmethod
    def from_design_points(cls, model: SoCPerfModel, result, indices,
                           *, req_mb: float = 0.1,
                           n_tg: Optional[int] = None,
                           flows: Optional[FlowPattern] = None
                           ) -> "BatchSimPlatform":
        """Bridge from the DSE layer: stack ``grid_sweep`` survivors (flat
        :class:`~repro.core.dse.SweepResult` /
        :class:`~repro.core.dse.ChunkedSweepResult` indices) for one
        batched replay.

        Vectorized: the per-design ``(B, A)`` replication/placement arrays
        and the ``(B, I)`` per-island rate matrix come straight from one
        ``result.design_arrays`` decode of the flat indices — per-island
        independent rates included — without materializing B DesignPoints
        or SimPlatforms (bit-identical to stacking
        ``SimPlatform.from_design_point`` per index, tested)."""
        n_tg = result.n_tg if n_tg is None else n_tg
        idx = np.asarray(indices, dtype=np.int64)
        wls = tuple(result.workloads)
        names = tuple(w.name for w in wls)
        assert len(set(names)) == len(names), "duplicate tile names"
        da = result.design_arrays(idx)
        B, A = da["k"].shape
        pos_idx = positions_to_indices(model.noc, da["pos"])
        mem_idx = pos_index(model.noc, model.mem_pos)
        assert not np.any(pos_idx == mem_idx), "tile placed on MEM"
        for a in range(A):
            for b in range(a + 1, A):
                assert not np.any(pos_idx[:, a] == pos_idx[:, b]), \
                    "tile collision (invalid sweep point selected)"
        specs = tuple(IslandSpec(n, (n,), TILE_LADDER, 1.0)
                      for n in names)
        specs += (IslandSpec("noc_mem", ("NOC", "MEM"), NOC_LADDER, 1.0),)

        def tile_const(vals):
            return np.broadcast_to(
                np.asarray(vals, dtype=np.float64), (B, A)).copy()

        return cls(
            model=model, islands=IslandConfig(specs), names=names,
            base_mbps=tile_const([w.base_mbps for w in wls]),
            wire_share=tile_const([w.wire_share for w in wls]),
            k=da["k"], pos_idx=pos_idx.astype(np.int64),
            req_mb=np.full((B, A), float(req_mb)),
            rates=da["rates"], f_tg=da["f_tg"], n_tg=int(n_tg),
            flows=flows)

    def design(self, b: int) -> SimPlatform:
        """Materialize design ``b`` as a sequential :class:`SimPlatform`
        (the differential-test / drill-down path)."""
        specs = tuple(dataclasses.replace(spec, rate=float(self.rates[b, i]))
                      for i, spec in enumerate(self.islands.islands))
        return SimPlatform(
            model=self.model,
            islands=dataclasses.replace(self.islands, islands=specs),
            names=self.names, base_mbps=self.base_mbps[b].copy(),
            wire_share=self.wire_share[b].copy(), k=self.k[b].copy(),
            pos_idx=self.pos_idx[b].copy(), req_mb=self.req_mb[b].copy(),
            n_tg=self.n_tg, f_tg=float(self.f_tg[b]), flows=self.flows)


# ---------------------------------------------------------------------------
# Result
# ---------------------------------------------------------------------------


@dataclass
class BatchSimResult:
    """Per-design outcome arrays of one batched replay (all ``(B,)``)."""
    n_designs: int
    ticks: int
    dt: float
    offered: object                     # float (shared trace) or (B,)
                                        # per-design totals (BatchTrace)
    completed: np.ndarray               # exit-stage services under a
                                        # chained FlowPattern (each
                                        # external request once)
    dropped: np.ndarray
    residual: np.ndarray
    throughput_rps: np.ndarray
    p50_latency_s: np.ndarray
    p99_latency_s: np.ndarray
    energy_j: np.ndarray
    energy_per_request_j: np.ndarray
    mean_power_w: np.ndarray
    swaps: np.ndarray                   # (B,) int64 actuator commits
    elapsed_wall_s: float               # whole batch, one clock
    backend: str = "numpy"
    telemetry: Optional[BatchTelemetry] = None   # None on the jax backend
    # fault/SLO ledgers, (B,) each (None on legacy constructions)
    dropped_slo: Optional[np.ndarray] = None
    dropped_fault: Optional[np.ndarray] = None
    retried: Optional[np.ndarray] = None

    @property
    def dropped_total(self) -> np.ndarray:
        """(B,) admission + SLO + stranded drops."""
        tot = np.asarray(self.dropped, dtype=np.float64).copy()
        if self.dropped_slo is not None:
            tot = tot + self.dropped_slo
        if self.dropped_fault is not None:
            tot = tot + self.dropped_fault
        return tot

    @property
    def drop_rate(self) -> np.ndarray:
        """(B,) dropped fraction of offered load (0 when nothing offered).
        Per-design floats match the sequential ``SimResult.drop_rate``."""
        off = np.asarray(self.offered, dtype=np.float64)
        tot = self.dropped_total
        return np.where(off > 0.0, tot / np.where(off > 0.0, off, 1.0), 0.0)

    @property
    def designs_per_s_wall(self) -> float:
        return (self.n_designs / self.elapsed_wall_s
                if self.elapsed_wall_s else 0.0)

    @property
    def requests_per_s_wall(self) -> float:
        return (float(self.completed.sum()) / self.elapsed_wall_s
                if self.elapsed_wall_s else 0.0)

    def summary(self) -> str:
        return (f"{self.n_designs} designs x {self.ticks} ticks "
                f"({self.backend}, {self.elapsed_wall_s:.2f}s wall, "
                f"{self.designs_per_s_wall:,.1f} designs/s): "
                f"p99 [{self.p99_latency_s.min() * 1e3:.2f}, "
                f"{self.p99_latency_s.max() * 1e3:.2f}]ms, "
                f"mJ/req [{self.energy_per_request_j.min() * 1e3:.3f}, "
                f"{self.energy_per_request_j.max() * 1e3:.3f}], "
                f"{int(self.swaps.sum())} DFS swaps")


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class BatchSimEngine:
    """Ticks B stacked designs through one trace, controllers in loop."""

    def __init__(self, platform: BatchSimPlatform, *,
                 config: SimConfig = SimConfig(),
                 controller: Optional[BatchControllerHarness] = None,
                 balancer: Optional[LoadBalancer] = None,
                 backend: str = "numpy",
                 faults: Optional[FaultSchedule] = None,
                 slo: Optional[SLOConfig] = None, observe=None,
                 devices=None, tech=None):
        assert backend in ("numpy", "jax", "pallas"), backend
        self.platform = platform
        # devices: None (single-device ground truth), an int, or "auto" —
        # the jax backend shards the design axis across this many devices
        self.devices = devices
        self.config = config
        self.controller = controller
        # physical DVFS model (core/voltage.py): tick energy becomes
        # power_scl * (P_static + P_dyn f V̂(f)^2) on every backend, and
        # the harness clamps commits to the node's legal [L, U] range;
        # None keeps the linear voltage proxy bit for bit
        self.tech = TechModel.coerce(tech)
        if self.tech is not None and controller is not None \
                and getattr(controller, "tech", None) is None:
            controller.tech = self.tech
        self.balancer = balancer
        self.backend = backend
        self.faults = faults
        self.slo = slo
        # run-time monitoring (observe.Observer or level string): the
        # numpy path accumulates the counter plane incrementally per tick,
        # the jax path carries accumulators through the scan (counters
        # level; full-trace tracing needs the Python-loop engines)
        self.observer = Observer.coerce(observe)
        self.last_state: Optional[TickState] = None
        self.last_histories = None      # (admitted, served) (T, B, A)
        self.last_fault_histories = None
        m = platform.model
        # per-design route->link incidence, stacked dense: (B, A, L) —
        # per-design routes of the (shared, name-keyed) flow pattern
        # against each design's own placement (tile->MEM when flows=None)
        cf = compile_flows(m, platform.names, platform.pos_idx,
                           platform.flows)
        self._compiled_flows = cf
        self._inc = cf.inc
        self._hop_counts = cf.hop_counts
        self._flow_demand = cf.demand
        self._forward = cf.forward
        self._t_comp_ref = (1.0 - platform.wire_share) / platform.k
        isl_names = platform.islands.names()
        self._island_of_tile = np.asarray(
            [isl_names.index(platform.islands.island_of(n).name)
             for n in platform.names], dtype=np.int64)
        try:
            self._noc_island = isl_names.index("noc_mem")
        except ValueError:
            self._noc_island = -1
        # compiled-scan cache: full run signature -> jitted scan.  The
        # key is EXPLICIT about everything the trace bakes in as a
        # constant (dt, controller plan, balancer layout, SLO semantics,
        # config scalars, device count) — two configurations that differ
        # in any baked constant MUST NOT share one executable (the PR 8
        # jit-cache collision bugfix; regression-tested).  Bounded LRU.
        self._jax_cache: "OrderedDict" = OrderedDict()

    # ------------------------------------------------------------ service
    def _service(self, rates: np.ndarray,
                 rate_override: Optional[np.ndarray] = None
                 ) -> Dict[str, np.ndarray]:
        """Service-time terms for a (B, I) rate matrix — the stacked
        analogue of ``SimEngine._service`` (recomputed only on commits).

        ``rate_override`` is the stuck-actuator hardware view: an (I,)
        row, NaN = follow the software rate.  It affects only the terms
        computed here — the caller's ``rates`` matrix (what telemetry
        records and the controller reasons about) stays the software
        view, exactly like the sequential engine."""
        p = self.platform
        B, A = p.n_designs, p.n_tiles
        if rate_override is not None:
            rates = np.where(np.isnan(rate_override), rates, rate_override)
        f_tile = rates[:, self._island_of_tile]              # (B, A)
        f_noc = (rates[:, self._noc_island] if self._noc_island >= 0
                 else np.ones(B))
        t_comp, t_wire, t_ref = p.model.service_time_terms_batch(
            wire_share=p.wire_share, k=p.k, f_acc=f_tile,
            f_noc=f_noc[:, None], f_tg=p.f_tg[:, None], n_tg=p.n_tg,
            hop_counts=self._hop_counts)
        return {"t_comp": np.broadcast_to(t_comp, (B, A)),
                "t_wire": np.broadcast_to(t_wire, (B, A)),
                "t_ref": np.broadcast_to(np.asarray(t_ref, float), (B, A)),
                "f_tile": f_tile, "f_noc": f_noc}

    def capacity_rps(self, rates: Optional[np.ndarray] = None) -> np.ndarray:
        """(B, A) uncontended per-tile service capacity (requests/s)."""
        svc = self._service(self.platform.rates if rates is None else rates)
        thr = self.platform.base_mbps * svc["t_ref"] / (
            svc["t_comp"] + svc["t_wire"])
        return thr / self.platform.req_mb

    def step_consts(self, dt: float) -> StepConsts:
        p, cfg = self.platform, self.config
        return StepConsts(
            base_mbps=p.base_mbps, req_mb=p.req_mb,
            hop_counts=self._hop_counts, inc=self._inc,
            own_demand=self._flow_demand, link_bw=p.model.noc.link_bw,
            max_slow=p.model.noc.max_slowdown,
            hop_latency=p.model.noc.hop_latency,
            noc_power_share=cfg.noc_power_share, dt=dt,
            max_queue=cfg.max_queue,
            dynamic_contention=cfg.dynamic_contention,
            forward=self._forward, tech=self.tech)

    def _check_trace(self, trace) -> None:
        p = self.platform
        assert trace.n_dests == p.n_tiles, (trace.n_dests, p.n_tiles)
        if isinstance(trace, BatchTrace):
            assert trace.n_designs == p.n_designs, \
                (trace.n_designs, p.n_designs)

    def _compile_faults(self, T: int) -> Optional[CompiledFaults]:
        if self.faults is None or not self.faults:
            return None
        p = self.platform
        return compile_faults(self.faults, ticks=T, names=p.names,
                              islands=p.islands, noc=p.model.noc)

    @staticmethod
    def _offered(trace):
        """External offered load: one float for a shared trace, per-design
        (B,) totals for a :class:`BatchTrace`."""
        if isinstance(trace, BatchTrace):
            return trace.n_requests
        return float(trace.arrivals.sum())

    def _completed(self, served_hist: np.ndarray) -> np.ndarray:
        """(B,) external completions.  Chained patterns count only
        exit-stage services (each request once); the chain-free
        expression is kept verbatim (bit-for-bit)."""
        if self._forward is None:
            return served_hist.sum(axis=(0, 2))
        return (served_hist
                * self._compiled_flows.exit_mask).sum(axis=(0, 2))

    # ---------------------------------------------------------------- run
    def run(self, trace) -> BatchSimResult:
        """Replay a shared :class:`Trace` (every design sees the same
        (T, A) arrivals) or a per-design :class:`BatchTrace` (T, B, A).

        Recorded as spans: ``cosim_prepare`` from here up to the tick
        loop (each backend closes it there), ``cosim_tick_loop`` (the
        region ``elapsed_wall_s`` times) and ``cosim_percentiles``."""
        with profiled("cosim_prepare") as prepare:
            if self.backend == "jax":
                return self._run_jax(trace, prepare)
            if self.backend == "pallas":
                return self._run_pallas(trace, prepare)
            return self._run_numpy(trace, prepare)

    def _run_numpy(self, trace, prepare) -> BatchSimResult:
        p, cfg = self.platform, self.config
        B, A, T, dt = p.n_designs, p.n_tiles, trace.ticks, trace.dt
        self._check_trace(trace)
        arrivals = trace.arrivals

        if self.controller is not None:
            assert self.controller.n_designs == B
            self.controller.begin_run()
            rates = self.controller.live_rates()
            swaps0 = self.controller.swaps.copy()
        else:
            rates = p.rates
            swaps0 = np.zeros(B, dtype=np.int64)

        # ---- fault/SLO compilation: one shared schedule drives all B
        # designs (faults are a property of the scenario, not the design);
        # every hook below is None-gated — a fault-free run is the exact
        # legacy loop, and a B=1 faulted run mirrors the sequential engine
        # tick for tick (same expressions, trailing-axis reductions).
        cf = self._compile_faults(T)
        slo = self.slo
        if slo is None and cf is not None:
            slo = SLOConfig()
        deadline = slo is not None and slo.deadline_s is not None
        has_tile = cf is not None and cf.has_tile
        has_link = cf is not None and cf.has_link
        has_stuck_rate = cf is not None and cf.has_stuck_rate
        recover = has_tile and slo.recovers and self.balancer is not None
        track = has_tile or deadline
        ev_by_tick = cf.events_by_tick() if cf is not None else {}
        applied_stuck = None
        svc = self._service(rates)

        st = TickState.zeros((B, A))
        consts = self.step_consts(dt)
        if deadline:
            consts = dataclasses.replace(
                consts, deadline_ticks=slo.deadline_s / dt)
        carry = np.zeros((B, A)) if consts.forward is not None else None
        prev_cap = (self.capacity_rps(rates) * dt
                    if self.balancer is not None else None)
        admitted_hist = np.zeros((T, B, A))
        served_hist = np.zeros((T, B, A))
        qdrop_hist = np.zeros((T, B, A)) if track else None
        fh = ({k: np.zeros((T, B)) for k in
               ("dropped", "dropped_slo", "dropped_fault", "retried",
                "queue", "carry")} if track else None)
        win_busy = np.zeros((B, A))
        win_served = np.zeros(B)
        win_ticks = 0
        ctl_busy = np.zeros((B, A))
        ctl_ticks = 0

        telem = BatchTelemetry(
            TelemetrySchema(islands=p.islands.names(), tiles=p.names),
            B, capacity=cfg.telemetry_capacity)

        # ---- monitoring (read-only; per tick the deferred capture costs
        # two preallocated slot writes — dyn row, link-load row — and the
        # counters are reconstructed vectorized from the histories the
        # loop already keeps, exactly like the sequential engine)
        ob = self.observer
        ocap = None
        if ob is not None and ob.enabled:
            ocap = ob.capture_sequential(
                T=T, consts=consts, lead=(B,),
                island_of_tile=self._island_of_tile,
                noc_island=self._noc_island, n_links=self._inc.shape[-1],
                n_islands=len(p.islands.names()),
                tile_alive=cf.tile_alive if has_tile else None,
                link_scale=cf.link_scale if has_link else None,
                tile_names=p.names, island_names=p.islands.names())
            ocap.on_service(0, svc)
            ob.begin_run()
            ob.emit(0, "run_start", subject="batch-numpy", ticks=T, dt=dt,
                    designs=B, level=ob.level)

        prepare.close()
        with profiled("cosim_tick_loop") as loop:
            for t_i in range(T):
                for ev in ev_by_tick.get(t_i, ()):
                    telem.event(t_i, ev["kind"],
                                **{k: v for k, v in ev.items()
                                   if k not in ("tick", "kind")})
                    if ob is not None:
                        ob.emit_event_dict(t_i, ev)
                alive = cf.tile_alive[t_i] if has_tile else None
                lscale = cf.link_scale[t_i] if has_link else None
                if has_stuck_rate:
                    row = cf.stuck_rate[t_i]
                    if applied_stuck is None or not np.array_equal(
                            row, applied_stuck, equal_nan=True):
                        applied_stuck = row
                        svc = self._service(rates, rate_override=applied_stuck)
                        if ocap is not None:
                            ocap.on_service(t_i, svc)

                respill = stranded_exit = None
                if has_tile and slo.on_kill != "wait":
                    st.queue, st.retry_q, respill, fdrop = respill_stranded(
                        st.queue, st.retry_q, alive,
                        self.balancer if recover else None)
                    st.dropped_fault = st.dropped_fault + fdrop.sum(axis=-1)
                    if recover:
                        st.retried = st.retried + respill.sum(axis=-1)
                    stranded_exit = respill + fdrop

                arr = arrivals[t_i]
                if carry is not None:
                    arr = arr + carry
                retry_arr = None
                if self.balancer is not None:
                    arr = self.balancer.split(
                        arr, st.queue, prev_cap,
                        alive=alive if recover else None)
                    if recover:
                        retry_arr = self.balancer.split(respill, st.queue,
                                                        prev_cap, alive=alive)
                        arr = arr + retry_arr
                out = tick_step(st, arr, svc, consts, alive=alive,
                                link_scale=lscale, retry_in=retry_arr)
                if ocap is not None:
                    ocap.on_tick(t_i, out)
                if carry is not None:
                    carry = out.forwarded
                if self.balancer is not None:
                    prev_cap = out.cap_tick
                admitted_hist[t_i] = out.admitted
                served_hist[t_i] = out.served
                if track:
                    qd = qdrop_hist[t_i]
                    if stranded_exit is not None:
                        qd += stranded_exit
                    if out.slo_drop is not None:
                        qd += out.slo_drop
                    fh["dropped"][t_i] = st.dropped
                    fh["dropped_slo"][t_i] = st.dropped_slo
                    fh["dropped_fault"][t_i] = st.dropped_fault
                    fh["retried"][t_i] = st.retried
                    fh["queue"][t_i] = st.queue.sum(axis=-1)
                    fh["carry"][t_i] = (carry.sum(axis=-1)
                                        if carry is not None else 0.0)

                win_busy += st.busy
                win_served += out.served.sum(axis=-1)
                win_ticks += 1
                ctl_busy += st.busy
                ctl_ticks += 1

                if (cfg.telemetry_interval
                        and (t_i + 1) % cfg.telemetry_interval == 0):
                    cap_rps_now = out.cap_tick / dt
                    telem.record(
                        tick=t_i, f_noc=svc["f_noc"], island_rates=rates,
                        queue_depth=st.queue, busy=win_busy / win_ticks,
                        throughput_rps=win_served / (win_ticks * dt),
                        power_w=out.tile_power + out.noc_power,
                        link_util_max=out.rho.max(axis=-1, initial=0.0),
                        link_util_mean=out.rho.mean(axis=-1),
                        latency_est_s=(st.queue.sum(axis=-1)
                                       / np.maximum(cap_rps_now.sum(axis=-1),
                                                    1e-9)),
                        dropped=st.dropped, dropped_slo=st.dropped_slo,
                        dropped_fault=st.dropped_fault, retried=st.retried)
                    win_busy = np.zeros((B, A))
                    win_served = np.zeros(B)
                    win_ticks = 0

                if (self.controller is not None and cfg.control_interval
                        and (t_i + 1) % cfg.control_interval == 0):
                    t_wire_now = svc["t_wire"] * out.dyn
                    new_rates = self.controller.step(
                        tick=t_i,
                        busy=ctl_busy / max(ctl_ticks, 1),
                        boundness=t_wire_now / (self._t_comp_ref + t_wire_now),
                        pkts_in=st.pkts_in, pkts_out=st.pkts_out,
                        rtt=st.rtt_acc,
                        queue_ticks=st.queue / np.maximum(out.cap_tick, 1e-12),
                        dead=cf.island_dead[t_i] if has_tile else None,
                        stuck=(cf.stuck[t_i]
                               if cf is not None and cf.has_stuck else None))
                    ctl_busy = np.zeros((B, A))
                    ctl_ticks = 0
                    if new_rates is not None:
                        rates = new_rates
                        svc = self._service(rates, rate_override=applied_stuck)
                        if ocap is not None:
                            ocap.on_service(t_i + 1, svc)
                        committed = np.nonzero(
                            self.controller.last_committed)[0].tolist()
                        telem.event(t_i, "dfs_commit", designs=committed)
                        if ob is not None:
                            ob.emit(t_i, "dfs_commit", subject="batch",
                                    designs=committed)
        elapsed = loop.seconds
        if ocap is not None:
            # lazy: the vectorized reconstruction runs on the first
            # observer.counters read, not inside the engine's wall clock
            ob.attach_lazy(lambda: ocap.finalize(admitted_hist, served_hist,
                                                 qdrop_hist))
            ob.emit(max(T - 1, 0), "run_end", subject="batch-numpy",
                    designs=B)

        self.last_state = st
        self.last_histories = (admitted_hist, served_hist)
        self.last_fault_histories = (
            None if fh is None else {**fh, "queue_drops": qdrop_hist})
        return self._result(trace, admitted_hist, served_hist,
                            completed=self._completed(served_hist),
                            dropped=np.asarray(st.dropped, dtype=np.float64),
                            residual=st.queue.sum(axis=-1),
                            energy=np.asarray(st.energy, dtype=np.float64),
                            swaps=(self.controller.swaps - swaps0
                                   if self.controller is not None
                                   else np.zeros(B, dtype=np.int64)),
                            elapsed=elapsed, backend="numpy", telem=telem,
                            dropped_slo=np.asarray(st.dropped_slo,
                                                   dtype=np.float64),
                            dropped_fault=np.asarray(st.dropped_fault,
                                                     dtype=np.float64),
                            retried=np.asarray(st.retried,
                                               dtype=np.float64),
                            qdrops=qdrop_hist)

    def _result(self, trace, admitted_hist, served_hist, *, completed,
                dropped, residual, energy, swaps, elapsed, backend,
                telem, dropped_slo=None, dropped_fault=None, retried=None,
                qdrops=None, bins=None) -> BatchSimResult:
        """``bins``: the pending answer of
        :func:`~repro.sim.percentiles.certified_bins` where the histories
        were on the device; the percentiles are then taken from it, and
        only the designs it could not certify are recomputed here."""
        B, T, dt = self.platform.n_designs, trace.ticks, trace.dt
        with profiled("cosim_percentiles"):
            if bins is not None:
                from repro.sim.percentiles import percentiles_from_bins
                p50, p99 = percentiles_from_bins(
                    bins, admitted_hist, served_hist, dt, qdrops)
            else:
                p50 = np.empty(B)
                p99 = np.empty(B)
                for b in range(B):
                    p50[b], p99[b] = latency_percentiles(
                        admitted_hist[:, b], served_hist[:, b], dt,
                        queue_drops=None if qdrops is None
                        else qdrops[:, b])
        sim_seconds = T * dt
        return BatchSimResult(
            n_designs=B, ticks=T, dt=dt,
            offered=self._offered(trace),
            completed=completed, dropped=dropped, residual=residual,
            throughput_rps=(completed / sim_seconds if sim_seconds
                            else np.zeros(B)),
            p50_latency_s=p50, p99_latency_s=p99, energy_j=energy,
            energy_per_request_j=np.where(
                completed > 0, energy / np.maximum(completed, 1e-9),
                np.nan),
            mean_power_w=(energy / sim_seconds if sim_seconds
                          else np.zeros(B)),
            swaps=np.asarray(swaps, dtype=np.int64),
            elapsed_wall_s=elapsed, backend=backend, telemetry=telem,
            dropped_slo=dropped_slo, dropped_fault=dropped_fault,
            retried=retried)

    # ------------------------------------------------------------- jax
    def _control_plan(self):
        """Digest the (optional) controller into static arrays/params the
        traced scan can close over.  Supported in the jax backend: no
        controller, guard-only, and the vectorized membound/PID policies."""
        ctl = self.controller
        if ctl is None:
            return {"kind": "none"}
        from repro.core.dfs import BatchMemoryBoundPolicy, BatchPIDRatePolicy
        topo = ctl.topo
        names = np.asarray(topo.names)
        plan = {
            "topo": topo,
            "guard": ctl.queue_guard_ticks,
            "guard_release": ctl.guard_release_ticks,
            "guard_rate": ctl.guard_rate,
        }
        if ctl.policy is None:
            plan["kind"] = "guard"
        elif isinstance(ctl.policy, BatchMemoryBoundPolicy):
            plan["kind"] = "membound"
            plan["threshold"] = ctl.policy.threshold
            plan["low_rate"] = ctl.policy.low_rate
            plan["skip"] = (topo.fixed | (topo.counts == 0)
                            | (names == "noc_mem"))
        elif isinstance(ctl.policy, BatchPIDRatePolicy):
            pol = ctl.policy
            plan["kind"] = "pid"
            plan.update(target=pol.target, kp=pol.kp, ki=pol.ki, kd=pol.kd,
                        min_rate=pol.min_rate,
                        integral_clamp=pol.integral_clamp)
            plan["skip"] = (topo.fixed | (topo.counts == 0)
                            | np.isin(names, pol.skip))
        elif hasattr(ctl.policy, "jax_step"):
            # custom BatchPolicy lowered into the scan/kernel carry: the
            # policy ships its own jax-side step (see core/dfs.py
            # BatchJaxPolicy protocol) and the harness semantics —
            # guard latch, ladder quantization, masked dual-buffer
            # commit — stay in the shared control lowering
            pol = ctl.policy
            plan["kind"] = "custom"
            plan["policy"] = pol
            skip = (pol.skip_islands(topo)
                    if hasattr(pol, "skip_islands")
                    else (topo.fixed | (topo.counts == 0)))
            plan["skip"] = np.asarray(skip, dtype=bool)
        else:
            raise NotImplementedError(
                "jax backend supports controller=None, guard-only, "
                "BatchMemoryBoundPolicy, BatchPIDRatePolicy, or any "
                "policy implementing the jax_step protocol; got "
                f"{type(ctl.policy).__name__}")
        return plan

    # ------------------------------------------------- jax control plane
    def _jax_control(self, plan, ci: int, B: int):
        """Lower the digested controller plan to ONE jax-traceable control
        function shared by the ``lax.scan`` backend and the Pallas kernel
        (so the two fast paths cannot drift).

        Returns ``(control, pol_state0)``: ``control(rates, guard,
        pol_state, ctl_flag, obs, dead=None, stuck=None)`` applies the
        policy + guard latch + ladder quantization + masked dual-buffer
        commit and returns ``(rates, guard, pol_state, committed)``;
        ``pol_state0`` is the tuple of per-design policy-state arrays
        threaded through the carry (PID integral/prev-err, or whatever a
        custom ``jax_step`` policy declares via ``jax_state``).  ``obs``
        carries per-TILE signals (``util``, ``bound``, ``qt`` — each
        ``(B, A)``); island aggregation happens here so both backends
        share it.  ``control`` is None for an open-loop run.
        """
        import jax
        import jax.numpy as jnp
        kind = plan["kind"]
        if kind == "none":
            return None, (), None
        topo = plan["topo"]
        # numpy, not jnp: the Pallas backend must feed these through
        # kernel inputs (captured array constants are rejected), so the
        # closure converts lazily (or takes a ``consts=`` override)
        cst = {"membership": np.asarray(topo.membership),       # (I, A)
               "counts_safe": np.where(topo.counts > 0,
                                       topo.counts, 1.0),
               "counts_pos": np.asarray(topo.counts > 0),
               "fixed": np.asarray(topo.fixed),
               "levels": np.asarray(topo.ladder_levels),        # (I, Lmax)
               "skip": np.asarray(plan.get(
                   "skip", np.ones(len(topo.names), dtype=bool)))}
        I = len(topo.names)
        pol = plan.get("policy")
        # Physical DVFS: the harness's tech model (injected by the engine
        # at construction when it has one) supplies the legal [L, U]
        # ratio range; baked as compile-time floats, keyed in the jit
        # cache via the _scan_cache_sig tech slot.
        tech = getattr(self.controller, "tech", None)
        tech_lo = None if tech is None else float(tech.l_bound)
        tech_hi = None if tech is None else float(tech.u_bound)
        if tech is not None:
            # (I, Lmax) mask of ladder levels inside [L, U]: quantization
            # snaps clamped requests to the nearest LEGAL level (the +inf
            # ladder padding is illegal by construction); islands whose
            # ladder lies fully outside fall back to every real level
            lvq = cst["levels"]
            legal = (lvq >= tech_lo) & (lvq <= tech_hi)
            cst["tech_legal"] = np.where(
                legal.any(axis=-1, keepdims=True), legal,
                np.isfinite(lvq))

        if kind == "pid":
            ctlp = self.controller.policy
            if ctlp._integral is not None:
                pol_state0 = (np.asarray(ctlp._integral),
                              np.asarray(ctlp._prev_err),
                              np.ones((B, 1), dtype=bool))
            else:
                pol_state0 = (np.zeros((B, I)), np.zeros((B, I)),
                              np.zeros((B, 1), dtype=bool))
        elif kind == "custom":
            pol_state0 = tuple(np.asarray(s) for s in pol.jax_state(B, I))
        else:
            pol_state0 = ()

        def on_ctl(ctl_flag, new, old):  # repro: traced
            # where(ctl_flag, new, old); Mosaic cannot select between
            # boolean vectors, so booleans take the and/or form
            if new.dtype == jnp.bool_:
                return (ctl_flag & new) | (~ctl_flag & old)
            return jnp.where(ctl_flag, new, old)

        def control(rates, guard, pol_state, ctl_flag, obs,  # repro: traced
                    dead=None, stuck=None, consts=None):
            c = (consts if consts is not None
                 else {kk: jnp.asarray(vv) for kk, vv in cst.items()})
            membership = c["membership"]
            counts_safe = c["counts_safe"]
            counts_pos = c["counts_pos"]
            fixed = c["fixed"]
            levels = c["levels"]
            skip = c["skip"]
            util_i = jnp.dot(obs["util"], membership.T,
                             precision=HIGHEST) / counts_safe
            bound_i = jnp.dot(obs["bound"], membership.T,
                              precision=HIGHEST) / counts_safe
            qt = obs["qt"]
            qt_i = jnp.where(membership[None, :, :] > 0,
                             qt[:, None, :], -jnp.inf).max(axis=-1)
            qt_i = jnp.where(counts_pos, qt_i, 0.0)

            valid = jnp.zeros(rates.shape, dtype=bool)
            req = rates
            if kind == "membound":
                req = jnp.where(bound_i >= plan["threshold"],
                                plan["low_rate"], 1.0)
                valid = ~skip[None, :] & jnp.ones_like(valid)
            elif kind == "pid":
                pid_i, pid_prev, pid_has = pol_state
                err = jnp.where(skip[None, :], 0.0,
                                util_i - plan["target"])
                i_term = jnp.clip(pid_i + err,
                                  -plan["integral_clamp"],
                                  plan["integral_clamp"])
                d_term = jnp.where(pid_has, err - pid_prev, 0.0)
                new = (rates + plan["kp"] * err + plan["ki"] * i_term
                       + plan["kd"] * d_term)
                req = jnp.clip(new, plan["min_rate"], 1.0)
                valid = ~skip[None, :] & jnp.ones_like(valid)
                pol_state = (on_ctl(ctl_flag, i_term, pid_i),
                             on_ctl(ctl_flag, err, pid_prev),
                             pid_has | ctl_flag)
            elif kind == "custom":
                obs_i = {"util": util_i, "boundness": bound_i,
                         "queue_ticks": qt_i}
                req_raw, new_state = pol.jax_step(rates, obs_i,
                                                  tuple(pol_state))
                # NaN = "no request" (the numpy BatchPolicy contract)
                req_raw = jnp.where(skip[None, :], jnp.nan, req_raw)
                valid = ~jnp.isnan(req_raw)
                req = jnp.where(valid, req_raw, rates)
                pol_state = tuple(
                    jax.tree_util.tree_map(
                        lambda n, o: on_ctl(ctl_flag, n, o),
                        tuple(new_state), tuple(pol_state)))

            if plan["guard"] is not None:
                # where(qt > guard, True, where(qt < release, False,
                # guard)) in boolean algebra (see on_ctl)
                latch = ((qt_i > plan["guard"])
                         | (~(qt_i < plan["guard_release"]) & guard))
                latch = latch & ~fixed[None, :]
                if dead is not None:     # dead islands drop out of latch
                    latch = latch & ~dead[None, :]
                req = jnp.where(latch, plan["guard_rate"], req)
                valid = valid | latch
                guard = on_ctl(ctl_flag, latch, guard)

            if tech_lo is not None:
                # clamp commits into the node's legal DVFS ratio range
                # (NaN "no request" entries pass through jnp.clip)
                req = jnp.clip(req, tech_lo, tech_hi)

            d = jnp.abs(levels[None, :, :] - req[:, :, None])
            if tech_lo is not None:     # illegal levels can't win argmin
                d = jnp.where(c["tech_legal"][None, :, :], d, jnp.inf)
            # nearest level, first on ties (argmin's rule), as a one-hot
            # select: Mosaic lowers no 3-D gather
            pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 2)
            first = jnp.where(d == d.min(axis=-1, keepdims=True), pos,
                              d.shape[-1]).min(axis=-1, keepdims=True)
            qz = jnp.where(pos == first, levels[None, :, :],
                           -jnp.inf).max(axis=-1)
            changed = valid & ~fixed[None, :] & (qz != rates) & ctl_flag
            if dead is not None:        # no hardware to commit to
                changed = changed & ~dead[None, :]
            if stuck is not None:       # actuator write never lands
                changed = changed & ~stuck[None, :]
            rates = jnp.where(changed, qz, rates)
            committed = changed.any(axis=-1)    # changed implies ctl_flag
            return rates, guard, pol_state, committed

        return control, pol_state0, cst

    def _control_writeback(self, plan, ratesF, guardF, swapsF, polF,
                           swaps_before):
        """Push the scan/kernel's evolved controller state back into the
        Python-side harness/policy objects (shared by jax and pallas)."""
        ctl = self.controller
        if ctl is None:
            return
        ctl.rates = np.asarray(ratesF, dtype=np.float64)
        ctl._guard_active = np.asarray(guardF, dtype=bool)
        ctl.swaps = swaps_before + np.asarray(swapsF).astype(np.int64)
        ctl.versions = ctl.versions + np.asarray(swapsF).astype(np.int64)
        if plan["kind"] == "pid":
            ctl.policy._integral = np.asarray(polF[0], dtype=np.float64)
            ctl.policy._prev_err = np.asarray(polF[1], dtype=np.float64)
        elif plan["kind"] == "custom" and hasattr(plan["policy"],
                                                 "jax_sync"):
            plan["policy"].jax_sync(tuple(np.asarray(s) for s in polF))

    # --------------------------------------------- jit-cache bookkeeping
    def _policy_digest(self, plan):
        """Hashable digest of everything the control lowering bakes into
        the traced function as a compile-time constant."""
        kind = plan["kind"]
        if kind == "none":
            return ("none",)
        topo = plan["topo"]
        items = [kind, plan["guard"], plan["guard_release"],
                 plan["guard_rate"],
                 np.asarray(topo.membership).tobytes(),
                 np.asarray(topo.counts).tobytes(),
                 np.asarray(topo.fixed).tobytes(),
                 np.asarray(topo.ladder_levels).tobytes(),
                 np.asarray(plan.get("skip", ())).tobytes()]
        if kind == "membound":
            items += [plan["threshold"], plan["low_rate"]]
        elif kind == "pid":
            items += [plan[kk] for kk in ("target", "kp", "ki", "kd",
                                          "min_rate", "integral_clamp")]
        elif kind == "custom":
            pol = plan["policy"]
            if hasattr(pol, "jax_cache_key"):
                items.append(pol.jax_cache_key())
            else:
                # identity + scalar attrs: a retuned policy (same object,
                # new gains) must miss the cache
                items.append((type(pol).__module__,
                              type(pol).__qualname__, id(pol)))
                items.append(tuple(sorted(
                    (kk, vv) for kk, vv in vars(pol).items()
                    if isinstance(vv, (bool, int, float, str)))))
        return tuple(items)

    def _balancer_digest(self):
        lb = self.balancer
        if lb is None:
            return None
        return (lb.mode, np.asarray(lb.membership).tobytes(),
                np.asarray(lb.group_of).tobytes(),
                np.asarray(lb.covered).tobytes())

    def _scan_cache_sig(self, *, T, ci, dt, B, D, arrivals_ndim,
                        fault_key, plan, slo):
        """The ONE canonical scan-jit cache signature.

        Every Python-level constant the traced ``run_scan`` closure
        bakes in must be keyed here (``SCAN_SIG_FIELDS`` names the
        slots; ``tests/test_analysis.py`` enumerates them and the
        RPR002 rule pass checks completeness statically).  Keeping the
        construction in a single helper means a future knob added to
        the scan cannot be forgotten at one of several call sites."""
        p, cfg, m = self.platform, self.config, self.platform.model
        return ("scan", T, ci, dt, B, D, arrivals_ndim, fault_key,
                self._policy_digest(plan), self._balancer_digest(),
                (cfg.max_queue, cfg.dynamic_contention,
                 cfg.noc_power_share),
                (m.own_demand, m.tg_demand, m.noc.link_bw,
                 m.noc.max_slowdown, m.noc.hop_latency,
                 m.hop_latency_share,
                 1.0 + m.hop_latency_share * m._ref_hops(), p.n_tg),
                None if slo is None else (slo.on_kill, slo.recovers,
                                          slo.deadline_s),
                (None if self.tech is None else self.tech.key,
                 None if getattr(self.controller, "tech", None) is None
                 else self.controller.tech.key))

    def _cached_scan(self, sig, build):
        """Look up / build the jitted scan for an explicit signature.
        Bounded LRU (``_SCAN_CACHE_MAX``): long-lived engines driven
        through many trace lengths / schedules can't pin one executable
        per configuration forever."""
        fn = self._jax_cache.get(sig)
        if fn is not None:
            self._jax_cache.move_to_end(sig)
            return fn
        fn = build()
        get_profiler().count("tick_loop_builds")
        self._jax_cache[sig] = fn
        while len(self._jax_cache) > _SCAN_CACHE_MAX:
            self._jax_cache.popitem(last=False)
        return fn

    def _run_jax(self, trace, prepare) -> BatchSimResult:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from repro import shard as shard_mod
        from repro.core.perfmodel import (P_DYN_W, P_STATIC_W, V_BASE,
                                          V_SLOPE)
        from repro.sim.percentiles import certified_bins

        p, cfg = self.platform, self.config
        B, A, T, dt = p.n_designs, p.n_tiles, trace.ticks, trace.dt
        self._check_trace(trace)
        m = p.model
        plan = self._control_plan()
        kind = plan["kind"]
        ctl = self.controller
        ci = cfg.control_interval if (ctl is not None
                                      and cfg.control_interval) else 0
        is_ctl = np.zeros(T, dtype=bool)
        if ci:
            is_ctl[ci - 1::ci] = True
        D = shard_mod.resolve_devices(self.devices)
        Bp = shard_mod.shard_len(B, D)

        # ----- replicated statics (shared across designs; safe to close
        # over even under shard_map) — per-DESIGN arrays travel through
        # the ``pd`` argument instead so the design axis can shard
        island_of_tile = jnp.asarray(self._island_of_tile)
        noc_idx = self._noc_island
        own = m.own_demand                  # static TG-saturation term
        demand = jnp.asarray(np.asarray(self._flow_demand,
                                        dtype=np.float64))  # live link loads
        has_fwd = self._forward is not None
        fwdM = jnp.asarray(self._forward) if has_fwd else None
        lb = self.balancer
        if lb is not None:
            lbM = jnp.asarray(lb.membership)
            lb_gof = jnp.asarray(lb.group_of)
            lb_cov = jnp.asarray(lb.covered)
            lb_mode = lb.mode

            def lb_split(arr, queue, cap, alive=None):
                if lb_mode == "even":
                    w = jnp.ones_like(arr)
                elif lb_mode == "capacity":
                    w = cap
                else:
                    w = cap / (1.0 + queue)
                # sanitize + dead-replica masking, as LoadBalancer.split
                w = jnp.where(jnp.isfinite(w) & (w > 0.0), w, 0.0)
                if alive is not None:
                    w = w * alive
                tot = jnp.einsum("ba,ga->bg", arr, lbM, precision=HIGHEST)
                wsum = jnp.einsum("ba,ga->bg", w, lbM, precision=HIGHEST)
                # all-zero weight groups fall back to an even split,
                # mirroring LoadBalancer.split
                w = jnp.where((wsum <= 0.0)[:, lb_gof], 1.0, w)
                wsum = jnp.einsum("ba,ga->bg", w, lbM, precision=HIGHEST)
                shared = tot[:, lb_gof] * (w / wsum[:, lb_gof])
                return jnp.where(lb_cov, shared, arr)
        tgd = m.tg_demand
        link_bw = m.noc.link_bw
        max_slow = m.noc.max_slowdown
        hop_lat = m.noc.hop_latency
        hop_share = m.hop_latency_share
        hopf0 = 1.0 + m.hop_latency_share * m._ref_hops()
        n_tg = p.n_tg
        dyn_on = cfg.dynamic_contention
        max_q = cfg.max_queue
        # monitoring statics: a Python bool baked into the trace (part of
        # the jit cache key) — level=off scans emit no extra ys and stay
        # byte-identical to the pre-observability trace.
        ob = self.observer
        observing = ob is not None and ob.enabled
        n_islands = len(p.islands.names())
        n_links = int(self._inc.shape[-1])

        # ----- fault/SLO statics: presence flags are Python bools baked
        # into the trace (part of the jit cache key); the per-tick mask
        # VALUES ride through the scanned xs pytree, so editing a schedule
        # of the same shape class never retraces
        cf = self._compile_faults(T)
        slo = self.slo
        if slo is None and cf is not None:
            slo = SLOConfig()
        deadline = slo is not None and slo.deadline_s is not None
        deadline_ticks = slo.deadline_s / dt if deadline else None
        has_tile = cf is not None and cf.has_tile
        has_link = cf is not None and cf.has_link
        has_stuck = cf is not None and cf.has_stuck
        has_stuck_rate = cf is not None and cf.has_stuck_rate
        recover = has_tile and slo.recovers and lb is not None
        drain = has_tile and slo.on_kill != "wait"
        track = has_tile or deadline

        control, pol0, _cctl = self._jax_control(plan, ci, B)

        def voltage2(f):
            v = V_BASE + V_SLOPE * f
            return v * v

        # Physical DVFS: the tech model's three coefficients bake in as
        # compile-time Python floats (keyed by the _scan_cache_sig tech
        # slot); tech=None keeps the legacy linear-proxy expressions
        # bit for bit.
        if self.tech is None:
            def _pw(f, busy):
                return (P_STATIC_W
                        + P_DYN_W * f * voltage2(f) * busy)
        else:
            t_ps, t_v0, t_v1 = self.tech.power_coeffs

            def _pw(f, busy):
                v = t_v0 + t_v1 * f
                return t_ps * (P_STATIC_W
                               + P_DYN_W * f * v * v * busy)

        def run_scan(pd, xs0, init):
            # per-design constants arrive as (possibly sharded) arguments
            inc = pd["inc"]
            hop_counts = pd["hop"]
            base_mbps = pd["base"]
            req_mb = pd["req"]
            w = pd["w"]
            k = pd["k"]
            t_comp_ref = pd["tcr"]
            f_tg = pd["ftg"]
            hopf = 1.0 + hop_share * hop_counts
            t_ref = (1.0 - w) + w * max(1.0, own) * hopf0

            def service(rates):
                f_tile = rates[:, island_of_tile]               # (B, A)
                f_noc = (rates[:, noc_idx] if noc_idx >= 0
                         else jnp.ones(rates.shape[0]))
                fa = jnp.maximum(f_tile, 1e-3)
                fn = jnp.maximum(f_noc, 1e-3)[:, None]
                load = own + tgd * f_tg[:, None] * n_tg
                slow = jnp.maximum(1.0, load / (link_bw * fn))
                t_comp = (1.0 - w) / (k * fa)
                t_wire = w * slow * hopf / fn
                return t_comp, t_wire, f_tile, f_noc

            def step(carry, xs):
                arr_t, ctl_flag = xs["arr"], xs["ctl"]
                (queue, busy, rtt, rates, guard, pol_state, ctl_busy,
                 dropped, energy, swaps, carry_fwd, prev_cap,
                 retry_q, dslo, dfault, retried) = carry
                alive_t = xs["alive"] if has_tile else None
                if has_stuck_rate:
                    srate_t = xs["srate"]      # (I,) NaN = follow software
                    rates_eff = jnp.where(jnp.isnan(srate_t)[None, :],
                                          rates, srate_t[None, :])
                else:
                    rates_eff = rates
                t_comp, t_wire, f_tile, f_noc = service(rates_eff)

                # drain work stranded on dead replicas BEFORE the split,
                # so the re-spill weights see the post-drain queues (as
                # the numpy engines do)
                respill = stranded_exit = None
                if drain:
                    dead_m = 1.0 - alive_t
                    stranded = queue * dead_m
                    s_retry = retry_q * dead_m
                    queue = queue - stranded
                    retry_q = retry_q - s_retry
                    if recover:
                        surv = jnp.einsum("a,ga->g", alive_t, lbM,
                                          precision=HIGHEST) > 0.0
                        can = lb_cov & surv[lb_gof]
                        respill = jnp.where(can, stranded - s_retry, 0.0)
                        fdrop = stranded - respill
                        retried = retried + respill.sum(axis=-1)
                        stranded_exit = respill + fdrop
                    else:
                        fdrop = stranded
                        stranded_exit = stranded
                    dfault = dfault + fdrop.sum(axis=-1)

                arr_eff = jnp.broadcast_to(arr_t, queue.shape)
                if has_fwd:
                    arr_eff = arr_eff + carry_fwd
                retry_arr = None
                if lb is not None:
                    arr_eff = lb_split(arr_eff, queue, prev_cap,
                                       alive=alive_t if recover else None)
                    if recover:
                        retry_arr = lb_split(respill, queue, prev_cap,
                                             alive=alive_t)
                        arr_eff = arr_eff + retry_arr
                q = queue + arr_eff
                adm = arr_eff
                if recover:
                    q0 = q              # retry-class mixing denominator
                    retry_q = retry_q + retry_arr
                if max_q != float("inf"):
                    over = jnp.maximum(q - max_q, 0.0)
                    q = q - over
                    adm = adm - over
                    dropped = dropped + over.sum(axis=-1)
                if dyn_on:
                    loads = jnp.einsum("ba,bal->bl", demand * busy, inc,
                                       precision=HIGHEST)
                    if has_link:
                        loads = loads / xs["lscale"]
                    rho = ((inc * loads[:, None, :]).max(axis=-1)
                           / (link_bw * f_noc[:, None]))
                    r = jnp.minimum(rho, 0.999)
                    dyn = jnp.minimum(1.0 + r / (2.0 * (1.0 - r)),
                                      max_slow)
                else:
                    loads = None
                    dyn = jnp.ones_like(q)
                cap = (base_mbps * t_ref / (t_comp + t_wire * dyn)
                       / req_mb) * dt
                if has_tile:
                    cap_nominal = cap
                    cap = cap * alive_t
                    served = jnp.minimum(q, cap)
                    queue = q - served
                    busy = jnp.where(cap > 0.0,
                                     served / jnp.where(cap > 0.0, cap,
                                                        1.0),
                                     0.0)
                else:
                    served = jnp.minimum(q, cap)
                    queue = q - served
                    busy = served / cap
                slo_drop = None
                if deadline:
                    horizon = ((cap if not has_tile else cap_nominal)
                               * deadline_ticks)
                    slo_drop = jnp.maximum(queue - horizon, 0.0)
                    queue = queue - slo_drop
                    dslo = dslo + slo_drop.sum(axis=-1)
                if recover:
                    retry_q = retry_q * jnp.where(
                        q0 > 0.0, queue / jnp.where(q0 > 0.0, q0, 1.0),
                        0.0)
                rtt = rtt + hop_counts * dyn * hop_lat
                if has_fwd:
                    carry_fwd = jnp.einsum("ba,aj->bj", served, fwdM,
                                           precision=HIGHEST)
                if lb is not None:
                    prev_cap = cap

                tp = _pw(f_tile, busy)
                if has_tile:            # dead tiles are power-gated
                    tp = tp * alive_t
                tile_power = jnp.sum(tp, axis=-1)
                noc_power = cfg.noc_power_share * _pw(f_noc, 1.0)
                energy = energy + (tile_power + noc_power) * dt
                ctl_busy = ctl_busy + busy

                if control is not None:
                    t_wire_now = t_wire * dyn
                    obs = {"util": ctl_busy / max(ci, 1),
                           "bound": t_wire_now / (t_comp_ref
                                                  + t_wire_now),
                           "qt": queue / jnp.maximum(cap, 1e-12)}
                    rates, guard, pol_state, committed = control(
                        rates, guard, pol_state, ctl_flag, obs,
                        dead=xs["dead"] if has_tile else None,
                        stuck=xs["stuck_m"] if has_stuck else None)
                    swaps = swaps + committed
                ctl_busy = jnp.where(ctl_flag, 0.0, ctl_busy)
                carry = (queue, busy, rtt, rates, guard, pol_state,
                         ctl_busy, dropped, energy, swaps, carry_fwd,
                         prev_cap, retry_q, dslo, dfault, retried)
                if track:
                    qdrop_t = jnp.zeros_like(queue)
                    if stranded_exit is not None:
                        qdrop_t = qdrop_t + stranded_exit
                    if slo_drop is not None:
                        qdrop_t = qdrop_t + slo_drop
                    ys = (adm, served, qdrop_t)
                else:
                    ys = (adm, served)
                if observing:
                    # pure reads of the step's arrays, never fed back
                    # into the dynamics above; narrow float32 snapshots
                    obs_ys = {"cap": cap.astype(jnp.float32),
                              "dyn": dyn.astype(jnp.float32),
                              "stall": queue > STALL_EPS,
                              "rates": rates_eff.astype(jnp.float32)}
                    ys = ys + (obs_ys,)
                return carry, ys

            Bb = k.shape[0]
            zBA = jnp.zeros((Bb, A))
            zB = jnp.zeros(Bb)
            carry0 = (zBA, zBA, zBA, init["rates"], init["guard"],
                      tuple(init["pol"]), zBA, zB, zB,
                      jnp.zeros(Bb, dtype=jnp.int32), zBA, init["cap"],
                      zBA, zB, zB, zB)
            return lax.scan(step, carry0, xs0)

        if ctl is not None:
            ctl.begin_run()
            rates0 = ctl.live_rates()
            guard0 = ctl._guard_active
            swaps_before = ctl.swaps.copy()
        else:
            rates0 = p.rates
            guard0 = np.zeros((B, n_islands), dtype=bool)
            swaps_before = None
        cap0 = (self.capacity_rps(rates0) * dt if lb is not None
                else np.zeros((B, A)))

        arrivals = np.asarray(trace.arrivals)
        xs0 = {"arr": arrivals, "ctl": is_ctl}
        if has_tile:
            xs0["alive"] = np.asarray(cf.tile_alive)
            xs0["dead"] = np.asarray(cf.island_dead)
        if has_link:
            xs0["lscale"] = np.asarray(cf.link_scale)
        if has_stuck:
            xs0["stuck_m"] = np.asarray(cf.stuck)
        if has_stuck_rate:
            xs0["srate"] = np.asarray(cf.stuck_rate)
        pd = {"inc": np.asarray(self._inc),
              "hop": np.asarray(self._hop_counts, dtype=np.float64),
              "base": p.base_mbps, "req": p.req_mb, "w": p.wire_share,
              "k": p.k, "tcr": self._t_comp_ref, "ftg": p.f_tg}
        init = {"rates": np.asarray(rates0), "guard": np.asarray(guard0),
                "cap": np.asarray(cap0), "pol": tuple(pol0)}
        if Bp != B:
            # pad the design axis to a device multiple with copies of
            # design 0 (computed, then discarded — sliced off below)
            pad = lambda a: shard_mod.pad_axis(np.asarray(a), D)  # noqa
            pd = {kk: pad(vv) for kk, vv in pd.items()}
            init = {"rates": pad(init["rates"]),
                    "guard": pad(init["guard"]), "cap": pad(init["cap"]),
                    "pol": tuple(pad(s) for s in init["pol"])}
            if arrivals.ndim == 3:
                xs0["arr"] = shard_mod.pad_axis(arrivals, D, axis=1)

        # ----- explicit jit-cache key: every Python-level constant the
        # traced function bakes in (the (T, ci, fault-flag) key of the
        # original implementation collided on dt, controller tuning,
        # balancer layout, SLO mode and config scalars)
        fault_key = (has_tile, has_link, has_stuck, has_stuck_rate,
                     recover, drain, track, deadline_ticks, observing)
        sig = self._scan_cache_sig(T=T, ci=ci, dt=dt, B=B, D=D,
                                   arrivals_ndim=arrivals.ndim,
                                   fault_key=fault_key, plan=plan,
                                   slo=slo)

        def build():
            if D <= 1:
                return jax.jit(run_scan)
            from jax.sharding import PartitionSpec
            mesh = shard_mod.device_mesh(D, "designs")

            def lead(a):
                return PartitionSpec(
                    *(("designs",) + (None,) * (np.ndim(a) - 1)))

            def rep(a):
                return PartitionSpec(*((None,) * np.ndim(a)))

            def timed(a):
                nd = np.ndim(a)
                if nd >= 3:             # (T, B, ...) per-design axis
                    return PartitionSpec(
                        *((None, "designs") + (None,) * (nd - 2)))
                return rep(a)

            in_specs = (
                jax.tree_util.tree_map(lead, pd),
                {kk: (timed(vv) if kk == "arr" else rep(vv))
                 for kk, vv in xs0.items()},
                jax.tree_util.tree_map(lead, init))
            out_sh = jax.eval_shape(run_scan, pd, xs0, init)
            out_specs = (
                jax.tree_util.tree_map(
                    lambda s: PartitionSpec(
                        *(("designs",) + (None,) * (len(s.shape) - 1))),
                    out_sh[0]),
                jax.tree_util.tree_map(
                    lambda s: PartitionSpec(
                        *((None, "designs")
                          + (None,) * (len(s.shape) - 2))),
                    out_sh[1]))
            return jax.jit(jax.shard_map(run_scan, mesh=mesh,
                                         in_specs=in_specs,
                                         out_specs=out_specs,
                                         check_vma=False))

        fn = self._cached_scan(sig, build)

        prepare.close()
        with profiled("cosim_tick_loop") as loop:
            carryF, ys = fn(pd, xs0, init)
            obs_ys = None
            if observing:
                *ys, obs_ys = ys
            if track:
                admitted, served, qdropT = ys
            else:
                (admitted, served), qdropT = ys, None
            # the percentiles run on the device while the host fetches
            bins = certified_bins(admitted, served, qdropT)
            qdrops = (None if qdropT is None
                      else np.asarray(qdropT, dtype=np.float64)[:, :B])
            (queueF, busyF, rttF, ratesF, guardF, polF, _ctlb, droppedF,
             energyF, swapsF, _fwdF, _capF, retryqF, dsloF, dfaultF,
             retriedF) = carryF
            polF = tuple(np.asarray(s)[:B] for s in polF)
            queueF, busyF, rttF, ratesF, guardF = [
                np.asarray(x)[:B]
                for x in (queueF, busyF, rttF, ratesF, guardF)]
            droppedF, energyF, swapsF, retryqF, dsloF, dfaultF, retriedF = [
                np.asarray(x)[:B]
                for x in (droppedF, energyF, swapsF, retryqF, dsloF,
                          dfaultF, retriedF)]
            admitted = np.asarray(admitted, dtype=np.float64)[:, :B]
            served = np.asarray(served, dtype=np.float64)[:, :B]
        elapsed = loop.seconds

        if obs_ys is not None:
            # lazy reconstruction from the raw per-tick ys on the first
            # counters read — the scan itself only paid the ys memcpys.
            # busy, the link loads and the power integral are replayed
            # host-side with the scan's own expressions (float64 over the
            # float32 snapshots, so they land within f32 rounding of the
            # numpy engine's counters)
            obs_ys = {kk: np.asarray(vv)[:, :B]
                      for kk, vv in obs_ys.items()}
            tile_alive_np = (np.asarray(cf.tile_alive, dtype=np.float64)
                             if has_tile else None)
            lscale_np = (np.asarray(cf.link_scale, dtype=np.float64)
                         if has_link else None)
            demand_np = np.asarray(self._flow_demand, dtype=np.float64)
            inc_np = np.asarray(self._inc, dtype=np.float64)
            iot_np = np.asarray(self._island_of_tile)

            def _jax_plane(o=obs_ys, admitted=admitted, served=served):
                stall = np.asarray(o["stall"])
                cap_t = np.asarray(o["cap"], dtype=np.float64)
                dyn_t = np.asarray(o["dyn"], dtype=np.float64)
                rates_t = np.asarray(o["rates"], dtype=np.float64)
                f_tile = rates_t[:, :, iot_np]                 # (T, B, A)
                f_noc = (rates_t[:, :, noc_idx] if noc_idx >= 0
                         else np.ones(rates_t.shape[:2]))      # (T, B)
                busy = np.where(cap_t > 0.0,
                                served / np.where(cap_t > 0.0, cap_t,
                                                  1.0),
                                0.0)
                pktf = np.asarray(p.req_mb) * 1e6 / PKT_BYTES
                hopc = np.asarray(self._hop_counts, dtype=np.float64)
                oh = np.zeros((A, n_islands))
                oh[np.arange(A), iot_np] = 1.0
                tile = {
                    "offered": admitted.sum(axis=0),
                    "invocations": served.sum(axis=0),
                    "busy_ticks": busy.sum(axis=0),
                    "stall_ticks": stall.sum(axis=0).astype(float),
                    "cap_sum": cap_t.sum(axis=0),
                    "hop_flits": (served * pktf * hopc).sum(axis=0),
                    "slowdown_sum": (dyn_t - 1.0).sum(axis=0)}
                if dyn_on:
                    # the wire load at tick t is driven by busy[t-1], as
                    # in the scan (busy starts the run at zero)
                    busy_prev = np.concatenate(
                        [np.zeros((1, B, A)), busy[:-1]], axis=0)
                    loads = np.einsum("tba,bal->tbl",
                                      demand_np * busy_prev, inc_np)
                    if lscale_np is not None:
                        loads = loads / lscale_np[:, None, :]
                    util = loads / (link_bw * f_noc[..., None])
                    link = {"flits": loads.sum(axis=0) / PKT_BYTES,
                            "util_sum": util.sum(axis=0),
                            "peak_util": util.max(axis=0, initial=0.0)}
                else:
                    link = {kk: np.zeros((B, n_links))
                            for kk in ("flits", "util_sum", "peak_util")}
                tp = _pw(f_tile, busy)
                if tile_alive_np is not None:
                    tp = tp * tile_alive_np[:, None, :]
                noc_p = cfg.noc_power_share * _pw(f_noc, 1.0)
                en = (tp.sum(axis=0) * dt) @ oh
                if noc_idx >= 0:
                    en[:, noc_idx] += noc_p.sum(axis=0) * dt
                return CounterPlane.from_arrays(
                    tile=tile, link=link, island={"energy_j": en},
                    ticks=np.full(B, float(T)), lead=(B,),
                    tile_names=p.names, island_names=p.islands.names())
            ob.attach_lazy(_jax_plane)

        self._control_writeback(plan, ratesF, guardF, swapsF, polF,
                                swaps_before)
        self.last_state = TickState(
            queue=queueF.astype(np.float64), busy=busyF.astype(np.float64),
            pkts_in=(admitted.sum(axis=0) * np.asarray(p.req_mb)
                     * 1e6 / PKT_BYTES),
            pkts_out=(served.sum(axis=0) * np.asarray(p.req_mb)
                      * 1e6 / PKT_BYTES),
            rtt_acc=rttF.astype(np.float64),
            dropped=droppedF.astype(np.float64),
            energy=energyF.astype(np.float64),
            retry_q=retryqF.astype(np.float64),
            dropped_slo=dsloF.astype(np.float64),
            dropped_fault=dfaultF.astype(np.float64),
            retried=retriedF.astype(np.float64))
        self.last_histories = (admitted, served)
        self.last_fault_histories = (
            None if qdrops is None else {"queue_drops": qdrops})
        return self._result(
            trace, admitted, served,
            completed=self._completed(served),
            dropped=droppedF.astype(np.float64),
            residual=queueF.astype(np.float64).sum(axis=-1),
            energy=energyF.astype(np.float64),
            swaps=swapsF.astype(np.int64), elapsed=elapsed,
            backend="jax", telem=None,
            dropped_slo=dsloF.astype(np.float64),
            dropped_fault=dfaultF.astype(np.float64),
            retried=retriedF.astype(np.float64),
            qdrops=qdrops, bins=bins)

    # ------------------------------------------------------------ pallas
    def _pallas_args(self, trace):
        """Check the run is in the kernel's scope and assemble the keyword
        arguments of :func:`repro.kernels.tick_sim.fused_tick_sim`
        (equally of ``tick_kernel_call``, which builds the kernel without
        running it).  Returns ``(args, plan, swaps_before)``."""
        p, cfg = self.platform, self.config
        B, A, T, dt = p.n_designs, p.n_tiles, trace.ticks, trace.dt
        self._check_trace(trace)
        if self._compile_faults(T) is not None:
            raise NotImplementedError(
                "pallas backend does not simulate fault schedules; "
                "use backend='jax'")
        if self.slo is not None:
            raise NotImplementedError(
                "pallas backend does not apply SLO semantics; "
                "use backend='jax'")
        if self.balancer is not None:
            raise NotImplementedError(
                "pallas backend does not run the load balancer; "
                "use backend='jax'")
        if self.observer is not None and self.observer.enabled:
            raise NotImplementedError(
                "pallas backend records no observer plane; "
                "use backend='jax' or 'numpy'")

        m = p.model
        plan = self._control_plan()
        ctl = self.controller
        ci = cfg.control_interval if (ctl is not None
                                      and cfg.control_interval) else 0
        control, pol0, cctl = self._jax_control(plan, ci, B)
        if ctl is not None:
            ctl.begin_run()
            rates0 = ctl.live_rates()
            guard0 = ctl._guard_active
            swaps_before = ctl.swaps.copy()
        else:
            rates0 = p.rates
            guard0 = np.zeros((B, len(p.islands.names())), dtype=bool)
            swaps_before = None

        arr = np.asarray(trace.arrivals)
        if arr.ndim == 2:               # shared trace -> (T, B, A)
            arr = np.broadcast_to(arr[:, None, :], (T, B, A))

        consts = {"base": p.base_mbps, "req": p.req_mb,
                  "w": p.wire_share, "k": p.k,
                  "hop": np.asarray(self._hop_counts, dtype=np.float64),
                  "tcr": self._t_comp_ref, "inc": np.asarray(self._inc),
                  "ftg": np.asarray(p.f_tg)[:, None]}
        scalars = {"dt": dt, "own": m.own_demand, "tgd": m.tg_demand,
                   "link_bw": m.noc.link_bw,
                   "max_slow": m.noc.max_slowdown,
                   "hop_lat": m.noc.hop_latency,
                   "hop_share": m.hop_latency_share,
                   "hopf0": 1.0 + m.hop_latency_share * m._ref_hops(),
                   "noc_share": cfg.noc_power_share, "n_tg": p.n_tg,
                   "dyn_on": cfg.dynamic_contention,
                   "max_q": cfg.max_queue, "ci": ci,
                   "noc_idx": self._noc_island,
                   "iot": np.asarray(self._island_of_tile),
                   "demand": np.asarray(self._flow_demand,
                                        dtype=np.float64),
                   "forward": (np.asarray(self._forward)
                               if self._forward is not None else None)}
        if self.tech is not None:
            # physical DVFS: bake the node's three power coefficients
            scalars["tech_on"] = True
            (scalars["t_ps"], scalars["t_v0"],
             scalars["t_v1"]) = self.tech.power_coeffs
        init = {"rates": np.asarray(rates0), "guard": np.asarray(guard0),
                "pol": tuple(pol0)}
        args = dict(arrivals=arr, consts=consts, scalars=scalars,
                    init=init, control_fn=control, control_consts=cctl)
        return args, plan, swaps_before

    def _run_pallas(self, trace, prepare) -> BatchSimResult:
        """The fused-kernel backend: the whole queue-update / contention /
        service / forward / control tick as ONE Pallas kernel
        (:func:`repro.kernels.tick_sim.fused_tick_sim`), T grid steps
        deep, per-tile state held in VMEM scratch between ticks.

        Scope: open-loop replay + every controller the jax backend's
        control lowering supports (membound / PID / guard / custom
        ``jax_step`` policies).  Faults, SLO semantics, the load
        balancer and the observer plane need scan-side bookkeeping this
        kernel does not carry — those runs raise ``NotImplementedError``
        and belong on ``backend="jax"``.  Differentially validated
        against the NumPy float64 engine (f32 tolerance) and the scan
        backend."""
        from repro.kernels.tick_sim import fused_tick_sim
        from repro.sim.percentiles import certified_bins
        p = self.platform
        B, A = p.n_designs, p.n_tiles
        args, plan, swaps_before = self._pallas_args(trace)
        prepare.close()
        with profiled("cosim_tick_loop") as loop:
            out = fused_tick_sim(**args)
            # the percentiles run on the device while the host fetches
            bins = certified_bins(out["adm"], out["served"])
            admitted = np.asarray(out["adm"], dtype=np.float64)
            served = np.asarray(out["served"], dtype=np.float64)
            queueF = np.asarray(out["queue"], dtype=np.float64)
            droppedF = np.asarray(out["dropped"], dtype=np.float64)
            energyF = np.asarray(out["energy"], dtype=np.float64)
            swapsF = np.asarray(np.rint(out["swaps"]), dtype=np.int64)
        elapsed = loop.seconds

        self._control_writeback(plan, out["rates"], out["guard"],
                                swapsF, out["pol"], swaps_before)
        zB = np.zeros(B)
        self.last_state = TickState(
            queue=queueF, busy=np.asarray(out["busy"], dtype=np.float64),
            pkts_in=(admitted.sum(axis=0) * np.asarray(p.req_mb)
                     * 1e6 / PKT_BYTES),
            pkts_out=(served.sum(axis=0) * np.asarray(p.req_mb)
                      * 1e6 / PKT_BYTES),
            rtt_acc=np.asarray(out["rtt"], dtype=np.float64),
            dropped=droppedF, energy=energyF,
            retry_q=np.zeros((B, A)), dropped_slo=zB.copy(),
            dropped_fault=zB.copy(), retried=zB.copy())
        self.last_histories = (admitted, served)
        self.last_fault_histories = None
        return self._result(
            trace, admitted, served,
            completed=self._completed(served),
            dropped=droppedF,
            residual=queueF.sum(axis=-1),
            energy=energyF, swaps=swapsF, elapsed=elapsed,
            backend="pallas", telem=None,
            dropped_slo=zB.copy(), dropped_fault=zB.copy(),
            retried=zB.copy(), bins=bins)
