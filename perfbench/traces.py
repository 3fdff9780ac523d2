"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` file ``jax.profiler`` writes.  On a TPU it
holds one plane per chip (``/device:TPU:<n>``) with an ``XLA Modules``
line (one event per program run, named ``jit_<function>(<hash>)``) and an
``XLA Ops`` line (one event per operation, named by its HLO text,
``%<op>.<n> = ...``), and a ``/host:CPU`` plane whose ``python`` line
holds the host's annotations and Python calls.  All events share one
clock, in nanoseconds.

:func:`reduce_trace` returns plain numbers and tables; nothing here
depends on the program under test.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
GAP_MIN_S = 50e-6          # shorter gaps lie between ops of one program


def op_name(event_name: str) -> str:
    """``%divide_reduce_fusion.2 = (f32[...]) ...`` -> ``divide_reduce_fusion``;
    ``jit_run_scan(1396...)`` -> ``jit_run_scan``."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    name = name.split("(", 1)[0]
    return re.sub(r"\.\d+$", "", name)


def load_planes(path: str) -> List[dict]:
    """Planes of one trace file as plain dicts: ``name`` and ``lines``,
    each line a ``name`` and an ``(n, 2)`` float array of [start, end] in
    seconds plus the event names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            names, spans = [], []
            for ev in line.events:
                names.append(ev.name)
                spans.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9))
            lines.append({"name": line.name, "names": names,
                          "spans": np.asarray(spans, dtype=np.float64)
                          .reshape(-1, 2)})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_trace_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def union(spans: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the given [start, end] rows."""
    if spans.size == 0:
        return spans.reshape(0, 2)
    s = spans[np.argsort(spans[:, 0], kind="stable")]
    ends = np.maximum.accumulate(s[:, 1])
    new = np.ones(len(s), dtype=bool)
    new[1:] = s[1:, 0] > ends[:-1]
    starts = s[new, 0]
    group = np.cumsum(new) - 1
    stops = np.zeros(starts.size)
    np.maximum.at(stops, group, s[:, 1])
    return np.stack([starts, stops], axis=1)


def _line(plane: dict, name: str) -> Optional[dict]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def _host_events(planes: Sequence[dict]) -> Tuple[List[str], np.ndarray]:
    """Events of the host's ``python`` lines (annotations and calls)."""
    names: List[str] = []
    spans = []
    for p in planes:
        if p["name"] != "/host:CPU":
            continue
        for ln in p["lines"]:
            if ln["name"] == "python" or ln["name"].startswith("python"):
                names += ln["names"]
                spans.append(ln["spans"])
    if not spans:
        return [], np.zeros((0, 2))
    return names, np.concatenate(spans)


def window_of(planes: Sequence[dict], annotation: str
              ) -> Optional[Tuple[float, float]]:
    """[start, end] of the first host event named ``annotation``."""
    names, spans = _host_events(planes)
    for n, s in zip(names, spans):
        if n == annotation:
            return float(s[0]), float(s[1])
    return None


def reduce_trace(planes: Sequence[dict], window: Tuple[float, float],
                 top: int = 10) -> dict:
    """Device busy time, time per module and per op, and idle gaps by
    what the host was doing, inside ``window`` (seconds).

    ``busy_s`` is the union of the intervals in which an op ran, averaged
    over the chips in the trace; ``modules`` and ``ops`` sum event
    durations by name over all chips; ``gaps`` sums the idle gaps of at
    least ``GAP_MIN_S`` by the innermost host event around each gap's
    middle (``<between ops>`` for shorter ones)."""
    lo, hi = window
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    busy, modules, ops = [], {}, {}
    gaps: Dict[str, float] = {}
    hnames, hspans = _host_events(planes)
    long_host = (hspans[:, 1] - hspans[:, 0]) >= GAP_MIN_S if hspans.size \
        else np.zeros(0, dtype=bool)
    hnames_l = [n for n, k in zip(hnames, long_host) if k]
    hspans_l = hspans[long_host] if hspans.size else hspans
    for p in devices:
        ln = _line(p, "XLA Ops")
        if ln is None or ln["spans"].size == 0:
            busy.append(0.0)
            continue
        inside = np.clip(ln["spans"], lo, hi)
        u = union(inside[inside[:, 1] > inside[:, 0]])
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        for n, (s, e) in zip(ln["names"], inside):
            if e > s:
                k = op_name(n)
                ops[k] = ops.get(k, 0.0) + (e - s)
        mods = _line(p, "XLA Modules")
        if mods is not None:
            for n, (s, e) in zip(mods["names"],
                                 np.clip(mods["spans"], lo, hi)):
                if e > s:
                    k = op_name(n)
                    modules[k] = modules.get(k, 0.0) + (e - s)
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        for s, e in edges:
            if e <= s:
                continue
            if e - s < GAP_MIN_S:
                key = "<between ops>"
            else:
                mid = 0.5 * (s + e)
                around = np.nonzero((hspans_l[:, 0] <= mid)
                                    & (hspans_l[:, 1] > mid))[0] \
                    if hspans_l.size else np.zeros(0, dtype=int)
                if around.size:
                    j = around[np.argmin(hspans_l[around, 1]
                                         - hspans_l[around, 0])]
                    key = hnames_l[j]
                else:
                    key = "<no host event>"
            gaps[key] = gaps.get(key, 0.0) + (e - s) / max(len(devices), 1)
    n_dev = max(len(devices), 1)
    return {"window_s": hi - lo, "busy_s": float(sum(busy)) / n_dev,
            "devices": len(devices), "modules": modules, "ops": ops,
            "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top]}


def module_seconds(red: dict, prefix: str) -> float:
    """Device seconds of modules whose name starts with ``prefix``."""
    return float(sum(v for k, v in red["modules"].items()
                     if k.startswith(prefix)))


def op_seconds(red: dict, name: str) -> float:
    """Device seconds of ops named ``name`` (numeric suffix removed)."""
    return float(red["ops"].get(name, 0.0))
