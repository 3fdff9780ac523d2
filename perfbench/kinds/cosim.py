"""Co-simulation cells: ``closed_loop_score`` re-rankings of a shortlist
of designs under a request trace, DFS control and faults, back to back.

Traffic file keys:
``survivors``   ``count`` designs, best first by ``by``, from the
                program's float64 host sweep of the configuration's space
                (``chunk_points`` as that sweep's chunking);
``trace``       the generator's parameters (:mod:`perfbench.traffic`);
``req_mb``      request size;
``control``     PID target and gains, queue guard, control interval;
``faults``      fault events (``kill_tile``, ``stick_island``);
``slo``         ``deadline_s`` and ``on_kill``, or null;
``p99_sla_s``, ``max_drop_rate``: the ranking's limits, or null;
``backend``, ``devices``: how the program runs the tick loop;
``pool``        traces made from the seed, used in turn by the window's
                rankings;
``check``       rankings compared with the reference: the last one and
                others drawn from the seed;
``traced_jobs`` rankings in the traced segment.
"""
from __future__ import annotations

import numpy as np

from perfbench import compare, counts, program, traffic
from perfbench import reference as ref

END_TO_END = "cosim_design_ticks_per_s"


def control_of(spec: dict) -> ref.Control:
    return ref.Control(
        interval=int(spec["control_interval"]), target=float(spec["target"]),
        kp=float(spec["kp"]), ki=float(spec["ki"]), kd=float(spec["kd"]),
        min_rate=float(spec["min_rate"]),
        integral_clamp=float(spec["integral_clamp"]),
        guard_ticks=float(spec["queue_guard_ticks"]),
        guard_release_ticks=float(spec["guard_release_ticks"]),
        guard_rate=float(spec["guard_rate"]))


class Workload:
    def __init__(self, ctx):
        self.cfg, self.tr, self.seed = ctx.cfg, ctx.traffic, ctx.seed
        self.m = ref.Model.from_config(self.cfg)
        self.ctl = control_of(self.tr["control"])
        self.faulted = bool(self.tr.get("faults"))
        self.traced_jobs = int(self.tr.get("traced_jobs", 2))
        # rankings run before the window (set-up): the first compiles or
        # loads the tick loop; a second, on another trace, checks that
        # nothing is left to compile
        self.warmup_jobs = 2
        self.outputs = {}

    def setup(self):
        from repro.core.dse import closed_loop_score, grid_sweep
        from repro.sim import SimConfig, Trace
        cfg, tr = self.cfg, self.tr
        model, wls = program.model_and_workloads(cfg)
        sv = tr["survivors"]
        res = grid_sweep(model, wls, chunk_points=sv.get("chunk_points"),
                         topk_track=int(sv["count"]),
                         **program.sweep_kwargs(cfg))
        self.indices = np.asarray(res.topk_indices(int(sv["count"]),
                                                   sv["by"]))
        self.designs = ref.designs(cfg, self.indices)
        self.n_tg = float(cfg["space"]["n_tg"])
        T = int(tr["trace"]["ticks"])
        self.dt = float(tr["trace"]["dt"])
        cap = (ref.capacity_rps(self.m, self.designs, self.n_tg,
                                float(tr["req_mb"]))
               if "capacity_share" in tr["trace"] else None)
        self.per_dest = traffic.per_dest_rates(tr["trace"], self.m.A, cap)
        self.pool = self.make_pool(self.seed)
        self.work = len(self.indices) * T
        kw = self._program_kwargs()
        self._call = lambda arr: closed_loop_score(
            res, Trace(arr, self.dt), model=model, indices=self.indices,
            sim_config=SimConfig(control_interval=self.ctl.interval), **kw)

    def make_pool(self, seed: int) -> list:
        return traffic.pool(self.tr["trace"], seed, self.per_dest,
                            int(self.tr["pool"]))

    def _program_kwargs(self) -> dict:
        from repro.core.dfs import BatchPIDRatePolicy
        from repro.sim import BatchControllerHarness, FaultSchedule, SLOConfig
        c, tr = self.tr["control"], self.tr

        def controller(p):
            pol = BatchPIDRatePolicy(
                target=float(c["target"]), kp=float(c["kp"]),
                ki=float(c["ki"]), kd=float(c["kd"]),
                min_rate=float(c["min_rate"]),
                integral_clamp=float(c["integral_clamp"]))
            return BatchControllerHarness(
                p.islands, p.rates, pol, tile_names=p.names,
                queue_guard_ticks=float(c["queue_guard_ticks"]),
                guard_release_ticks=float(c["guard_release_ticks"]),
                guard_rate=float(c["guard_rate"]))

        fs = None
        if tr.get("faults"):
            fs = FaultSchedule()
            for ev in tr["faults"]:
                if ev["kind"] == "kill_tile":
                    fs = fs.kill_tile(ev["tile"], start=int(ev["start"]),
                                      end=ev.get("end"))
                elif ev["kind"] == "stick_island":
                    fs = fs.stick_island(ev["island"], start=int(ev["start"]),
                                         end=ev.get("end"),
                                         rate=ev.get("rate"))
                else:
                    raise ValueError(f"unknown fault kind {ev['kind']!r}")
        slo = None if tr.get("slo") is None else SLOConfig(
            deadline_s=tr["slo"].get("deadline_s"),
            on_kill=tr["slo"].get("on_kill", "respill"))
        return dict(req_mb=float(tr["req_mb"]),
                    batch_controller_factory=controller,
                    backend=tr["backend"], fault_schedule=fs, slo=slo,
                    p99_sla_s=tr.get("p99_sla_s"),
                    max_drop_rate=tr.get("max_drop_rate"),
                    devices=tr.get("devices"))

    def job(self, i: int) -> dict:
        k = i % len(self.pool)
        s = self._call(self.pool[k])
        r = s.results[0]
        self.outputs[i] = {
            "completed": np.asarray(r.completed), "energy":
            np.asarray(r.energy_j), "p99": np.asarray(s.p99_latency_s),
            "swaps": np.asarray(r.swaps),
            "drop_rate": (None if s.drop_rate is None
                          else np.asarray(s.drop_rate)),
            "order": np.asarray(s.order), "trace": k}
        return {"work": self.work, "engine_s": float(r.elapsed_wall_s)}

    def end_to_end(self, jobs, window_s: float) -> dict:
        return {END_TO_END: sum(j["work"] for j in jobs) / window_s}

    def shape(self) -> dict:
        """The tick loop's sizes, for :func:`counts.tick_loop`."""
        B, A = self.designs.k.shape
        masks = self.faulted or self.tr.get("slo") is not None
        return {"ticks": int(self.tr["trace"]["ticks"]), "designs": B,
                "tiles": A, "islands": A + 1,
                "links": counts.mesh_links(self.m.rows, self.m.cols),
                "levels": max(len(ref.ladder(v))
                              for v in self.cfg["ladders"].values()),
                "control_interval": self.ctl.interval, "faults": masks,
                "histories": 3 if masks else 2}

    def release(self):
        self._call = None

    def sample(self, window: list) -> list:
        """The window's last ranking and ``check - 1`` more drawn from the
        seed, on other traces of the pool where the window used them."""
        want = int(self.tr.get("check", 2))
        last = window[-1]
        rest = [i for i in window[:-1]
                if self.outputs[i]["trace"] != self.outputs[last]["trace"]]
        if not rest:
            rest = window[:-1]
        rng = traffic.rng_for(self.seed, 1 << 20)
        extra = rng.permutation(rest)[:max(want - 1, 0)].tolist()
        return [last] + sorted(extra)

    def reference(self, arrivals: np.ndarray, prec=ref.F64):
        tr = self.tr
        lad = self.cfg["ladders"]
        sp = self.cfg["space"]
        ladders = [ref.ladder(lad[sp["acc_ladder"]])] * self.m.A \
            + [ref.ladder(lad[sp["noc_ladder"]])]
        T = arrivals.shape[0]
        faults = (ref.Faults.build(tr["faults"], T, self.m.names)
                  if tr.get("faults") else None)
        slo = tr.get("slo") or {}
        sim = ref.cosim(self.m, self.designs, arrivals, dt=self.dt,
                        n_tg=self.n_tg, req_mb=float(tr["req_mb"]),
                        ladders=ladders, control=self.ctl, faults=faults,
                        deadline_s=slo.get("deadline_s"),
                        drain_dead=slo.get("on_kill", "respill") != "wait",
                        prec=prec)
        sc = ref.score(sim, self.dt, p99_sla_s=tr.get("p99_sla_s"),
                       max_drop_rate=tr.get("max_drop_rate"),
                       faulted=self.faulted)
        return sim, sc

    def check(self, jobs) -> dict:
        numbers = None
        for i in self.sample([j["i"] for j in jobs]):
            got = self.outputs[i]
            sim, sc = self.reference(self.pool[got["trace"]])
            numbers = compare.worst(numbers, compare.cosim_numbers(
                got, sim, sc, self.faulted))
        return numbers
