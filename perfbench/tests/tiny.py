"""A copy of the benchmark at a size a CPU test run can hold.

The copy keeps every file of ``perfbench/`` and ``BENCHMARK.json`` and
changes only sizes: every fourth accelerator and ninth NoC level swept,
K in {1, 2}, 48 survivors, 300 ticks (the fault windows scaled with
them), 5,000-point chunks.  Widths (accelerators, islands, ladders,
positions, control, faults) stay as they are.
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TICKS = 300
SURVIVORS = 48


def _edit(path, fn):
    with open(path) as f:
        data = json.load(f)
    fn(data)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make(root: str, devices: int = 1) -> str:
    """Write the small copy under ``root``; returns ``root``.  Sweeps
    shard over at most ``devices`` devices."""
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    pb = os.path.join(root, "perfbench")

    def config(c):
        c["space"].update(acc_stride=4, noc_stride=9, ks=[1, 2])
        if len(c["space"]["tg_rates"]) > 2:
            c["space"]["tg_rates"] = [0.2, 1.0]

    def mix(t):
        if t["kind"] == "sweep":
            t.update(chunk_points=5000, topk_track=16, warmup=[{"ks": [1]}],
                     devices=min(t["devices"], devices))
            return
        t["survivors"]["count"] = SURVIVORS
        if t["survivors"].get("chunk_points"):
            t["survivors"]["chunk_points"] = 5000
        scale = TICKS / t["trace"]["ticks"]
        t["trace"]["ticks"] = TICKS
        for ev in t["faults"]:
            for k in ("start", "end"):
                if ev.get(k) is not None:
                    ev[k] = int(ev[k] * scale)
        t.update(pool=2, traced_jobs=1)

    for name in os.listdir(os.path.join(pb, "configs")):
        _edit(os.path.join(pb, "configs", name), config)
    for name in os.listdir(os.path.join(pb, "traffic")):
        _edit(os.path.join(pb, "traffic", name), mix)
    return root


def run(root: str, workload: str, seed: int = 2**31 + 5,
        seconds: float = 0.5, trace: bool = False) -> dict:
    """One run of ``workload`` in the small copy, without a chip."""
    from perfbench import harness
    return harness.run(root, workload, seed, seconds, trace,
                       process_start=0.0, on_chip=False)
