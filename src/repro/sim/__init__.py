"""Closed-loop SoC simulation: vectorized traffic replay + online DFS.

The run-time counterpart of the static DSE engine — replays request
traces through one concrete design while monitor-driven DFS controllers
retune island rates in the loop:

engine.py    — tick-based batched event loop (flat arrays, no per-request
               Python objects; service rates from the perfmodel kernel,
               contention from the NoC routing tables); the shared
               tick_step/TickState numeric core every engine runs
batch.py     — B design points co-simulated as ONE array program
               ((B, A) state, stacked incidence, vectorized DFS commits;
               numpy reference + jax.lax.scan backend; shared Trace or
               per-design BatchTrace arrival tensors)
flows.py     — FlowPattern: tile-to-tile streams + accelerator chains
               (stage completions feed the next stage), compiled per
               design into the incidence/hop/forward arrays the tick
               loop consumes (None == the legacy tile->MEM pattern)
traffic.py   — composable arrival-trace generators (constant, Poisson,
               diurnal, MMPP-bursty, replay) scaling to millions of
               requests; BatchTrace stacks/broadcasts per-design tensors
control.py   — controller harness: windowed C3 counter samples -> dfs
               policies -> dual-buffer actuator commits (scalar + the
               vectorized multi-design BatchControllerHarness) and the
               LoadBalancer admission policy for replicated islands
faults.py    — FaultSchedule (tile/island kills, link degradation, stuck
               actuators) compiled to per-tick availability/scale masks
               the tick loop consumes, plus SLOConfig (deadline drops,
               bounded retry of stranded work) — all three backends
               replay one schedule, bit-for-bit at B=1
telemetry.py — ring-buffer time series + JSON export (per-design rings
               for the batched engine), incl. drop/retry fault counters
percentiles.py — the batched engine's p50/p99 on the device (jax and
               pallas backends), certified equal to the host's float64
               latency_percentiles, which recomputes what it cannot certify
observe.py   — run-time monitoring: the per-tile/per-link/per-island
               hardware-counter plane (CounterPlane), schema'd
               control-plane tracing (ControlTrace/TraceEvent), the
               Observer level= knob (off/counters/full) every engine
               accepts via observe=, and the span and counter
               recorder (Profiler: phase totals, a ring of spans on the
               JAX profiler's clock, counters)
metrics.py   — MetricsRegistry (counter/gauge/histogram) rendering
               Prometheus text + JSON timeseries from telemetry and the
               counter plane

DSE bridge: ``core/dse.py:closed_loop_score`` re-ranks ``grid_sweep``
Pareto survivors by simulated tail latency and energy under dynamic
traffic — one batched replay for all survivors.
"""
from repro.sim.engine import (  # noqa: F401
    SimConfig, SimEngine, SimPlatform, SimResult, StepConsts, TickState,
    latency_percentiles, tick_step)
from repro.sim.batch import (  # noqa: F401
    BatchSimEngine, BatchSimPlatform, BatchSimResult)
from repro.sim.control import (  # noqa: F401
    BatchControllerHarness, BatchSample, ControlAction, ControllerHarness,
    IslandTopology, LoadBalancer)
from repro.sim.faults import (  # noqa: F401
    CompiledFaults, FaultSchedule, IslandKill, LinkDegrade, SLOConfig,
    StuckRate, TileKill, compile_faults, respill_stranded)
from repro.sim.flows import (  # noqa: F401
    CompiledFlows, FlowPattern, compile_flows)
from repro.sim.metrics import (  # noqa: F401
    MetricsRegistry, parse_prometheus_text, telemetry_timeseries)
from repro.sim.observe import (  # noqa: F401
    LEVELS, TRACE_KINDS, ControlTrace, CounterPlane, Observer, Profiler,
    TraceEvent, export_metrics, get_profiler, profiled, reset_profiler)
from repro.sim.telemetry import (  # noqa: F401
    BatchTelemetry, RingBuffer, Telemetry, TelemetrySchema,
    weighted_percentiles)
from repro.sim.traffic import (  # noqa: F401
    BatchTrace, Trace, constant_trace, diurnal_trace, mmpp_trace,
    poisson_trace, replay_trace, superpose, with_total)
