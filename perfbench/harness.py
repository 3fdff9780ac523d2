"""The benchmark's run: one cell, one process.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything else is found by name, in files of their own under
``perfbench/``:

``configs/<config>.json``   the deployment (model numbers, design space)
``traffic/<traffic>.json``  the mix; its ``kind`` picks ``kinds/<kind>.py``
``metrics/<metric>.py``     one reader per per-layer metric
``limits/<cell>.json``      the limits of the comparisons that decide
                            ``correct``, read for this cell
``peaks.json``              peak rates per ``device_kind``

A run sets up (load, build, warm every shape the cell uses), runs whole
jobs back to back until ``--seconds`` have passed, reads the device's
memory peak, frees the program's state, compares what the window produced
with the plain reference, and prints one JSON line.  With ``--trace 1``
it then traces a few more jobs and reports the per-layer metrics.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, List, Optional

from perfbench import compare, traces


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip the peaks table lacks."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def bench_dir(root: str) -> str:
    return os.path.join(root, "perfbench")


def find(root: str, sub: str, name: str, ext: str) -> str:
    path = os.path.join(bench_dir(root), sub, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{sub}/{name}{ext} not found under "
                                f"{bench_dir(root)}")
    return path


def load_reader(root: str, name: str):
    """The module ``metrics/<name>.py``: ``UNIT``, ``LAYER``, ``MOVES``,
    ``SOURCE`` and ``read(ctx)``."""
    path = find(root, "metrics", name, ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str):
    return importlib.import_module(f"perfbench.kinds.{kind}")


def load_limits(root: str, cell: str) -> dict:
    path = find(root, "limits", cell, ".json")
    return {k: float(v) for k, v in load_json(path)["limits"].items()}


def applies(metric: dict, cell: dict, e2e_names) -> bool:
    """Whether a cell reports a metric: it is listed in the metric's
    ``workloads``, or, for a metric without that key, it reports the
    end-to-end metric the metric moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric.get("moves") in e2e_names


def plan(root: str, workload: str) -> types.SimpleNamespace:
    """Everything a run of ``workload`` reads, found by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = load_json(find(root, "configs", cell["config"], ".json"))
    tr = load_json(find(root, "traffic", cell["traffic"], ".json"))
    kind = load_kind(tr["kind"])
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or applies(m, cell, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if applies(m, cell, names)]
    readers = {}
    for m in per_layer:
        r = load_reader(root, m["name"])
        for key in ("unit", "layer", "moves", "source"):
            if getattr(r, key.upper()) != m[key]:
                raise ValueError(f"metrics/{m['name']}.py says {key} "
                                 f"{getattr(r, key.upper())!r}, "
                                 f"BENCHMARK.json {m[key]!r}")
        readers[m["name"]] = r
    return types.SimpleNamespace(
        bench=bench, cell=cell, cfg=cfg, traffic=tr, kind=kind,
        end_to_end=e2e, per_layer=per_layer, readers=readers,
        limits=load_limits(root, cell["name"]),
        peaks=load_json(os.path.join(bench_dir(root), "peaks.json")))


def look_for_chip(chips: int, peaks: dict) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    seen = f"{d.platform} {d.device_kind!r} x{len(devs)}"
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX finds {seen}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {seen}")
    if d.device_kind not in peaks:
        raise NoChip(f"device_kind {d.device_kind!r} is not in peaks.json "
                     f"(JAX finds {seen})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileLog:
    """JAX's monitoring duration events, with the time they arrived."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events: List[tuple] = []

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((time.perf_counter(), float(duration)))

    def seconds(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.events if t0 <= t < t1)


def run_jobs(work, log: CompileLog, seconds: float, trace_dir=None):
    """Run the workload's warm-up jobs, then the window's until ``seconds``
    have passed, then, with ``trace_dir``, its traced jobs under the
    profiler.  Returns the three lists of jobs and the window's start and
    length.

    Every job is called from the one line below: JAX keeps the Python call
    stack in the source locations of a Pallas kernel, and they are part
    of the compilation cache's key, so a job called from anywhere else
    would compile its kernel again."""
    import jax
    phases = {"warmup": [], "window": [], "traced": []}
    phase = "warmup" if work.warmup_jobs else "window"
    t_window = time.perf_counter()
    window_s = 0.0
    annotation = None
    i = 0
    while phase is not None:
        tj = time.perf_counter()
        info = work.job(i)
        tk = time.perf_counter()
        info.update(i=i, wall_s=tk - tj, compile_s=log.seconds(tj, tk))
        phases[phase].append(info)
        i += 1
        n = len(phases[phase])
        if phase == "warmup" and n >= work.warmup_jobs:
            phase = "window"
            t_window = time.perf_counter()
        elif phase == "window" and tk - t_window >= seconds:
            window_s = time.perf_counter() - t_window
            phase = None
            if trace_dir is not None:
                phase = "traced"
                jax.profiler.start_trace(trace_dir)
                annotation = jax.profiler.TraceAnnotation("bench.traced")
                annotation.__enter__()
        elif phase == "traced" and n >= work.traced_jobs:
            annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            phase = None
    return phases, t_window, window_s


def reduce_traced(trace_dir: str) -> dict:
    """The trace of the traced jobs, reduced (:mod:`traces`)."""
    planes = traces.load_planes(traces.find_trace_file(trace_dir))
    win = traces.window_of(planes, "bench.traced")
    if win is None:
        raise RuntimeError("the trace holds no bench.traced annotation")
    return traces.reduce_trace(planes, win)


def finite(x: float):
    """A number JSON can carry: NaN as null, infinities as the largest
    float of their sign."""
    if math.isnan(x):
        return None
    if math.isinf(x):
        return math.copysign(sys.float_info.max, x)
    return x


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, process_start: float, on_chip: bool = True) -> dict:
    """One run; returns the result line's object.  ``on_chip=False``
    skips the look for a chip and the memory reading (tests on the CPU)."""
    p = plan(root, workload)
    chips = int(p.cell["chips"])
    if on_chip:
        device = look_for_chip(chips, p.peaks)
    else:
        device = {"platform": "cpu", "kind": "cpu", "count": 1}
    import jax
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    ctx = types.SimpleNamespace(cfg=p.cfg, traffic=p.traffic, seed=seed)
    work = p.kind.Workload(ctx)
    work.setup()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace \
        else None
    try:
        phases, t0, window_s = run_jobs(work, log, seconds, trace_dir)
        device["memory_peak_bytes"] = memory_peak(chips) if on_chip else 0
        red = reduce_traced(trace_dir) if trace else None
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    jobs, tjobs = phases["window"], phases["traced"]
    setup_s = t0 - process_start
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        rctx = types.SimpleNamespace(
            jobs=jobs, window_s=window_s, traced=tjobs, trace=red,
            shape=work.shape(), peak=p.peaks.get(device["kind"]))
        for m in p.per_layer:
            v = p.readers[m["name"]].read(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": [[k, v] for k, v in red["device_ops"]],
                     "idle_gaps": [[k, v] for k, v in red["idle_gaps"]]}
    else:
        e2e = work.end_to_end(jobs, window_s)
        e2e["setup_s"] = setup_s
        for m in p.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    work.release()
    gc.collect()
    t_check = time.perf_counter()
    numbers = work.check(jobs)
    judged = compare.judge(numbers, p.limits)
    print(f"timing setup_s={setup_s:.3f} window_s={window_s:.3f} "
          f"check_s={time.perf_counter() - t_check:.3f} job_walls="
          + ",".join(f"{j['wall_s']:.3f}" for j in jobs) + " job_compile_s="
          + ",".join(f"{j['compile_s']:.3f}" for j in jobs), file=sys.stderr)
    for k in sorted(set(numbers) - set(judged)):
        print(f"reading {k} = {numbers[k]!r} (not compared)", file=sys.stderr)
    correct = all(j["ok"] for j in judged.values())
    result = {"correct": correct, "attempted": len(jobs),
              "failed": 0 if correct else 1, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": finite(j["value"]),
                            "limit": j["limit"]}
                        for k, j in judged.items()}
    return result


def main(argv=None, *, root: Optional[str] = None,
         process_start: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.getcwd()
    if process_start is None:
        process_start = time.perf_counter()
    try:
        result = run(root, args.workload, args.seed, args.seconds,
                     bool(args.trace), process_start=process_start)
    except NoChip as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False))
    return 0
