"""Multi-device sharding benchmark: the sweep evaluator across devices.

Measures the sharded ``grid_sweep`` path at 1 vs N virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``) and appends the
speedup trajectory to ``BENCH_shard.json``.  Device count is fixed at
the first jax import, so each arm runs in its own subprocess with its
own ``XLA_FLAGS`` — the same pattern ``tests/test_distributed.py`` uses.

Two numbers per arm:

* ``eval_seconds`` — steady-state wall time of the device-side flat-point
  evaluator (``repro.core.dse._flat_point_evaluator``) on the first
  ``EVAL_POINTS`` points of a fixed space, which it decodes itself.  This is the computation ``shard_map`` actually partitions,
  so it is what the **>= 2x at 4 virtual devices** acceptance gate runs on.
* ``sweep_seconds`` — an end-to-end chunked ``grid_sweep(devices=N)``,
  which also pays the serial host-side gather/Pareto-merge work and is
  reported un-gated (Amdahl caps it below the evaluator speedup).

The gate is asserted only when the machine actually has >= ``GATE_DEVICES``
CPU cores (virtual devices on one core time-slice it — no speedup exists
to measure); below that the row records the measurement with
``"enforced": false``.  The benchmark refuses to run on an accelerator
backend: the chip belongs to one process, and ``chip_smoke.py --chips 4``
covers the multi-chip path there.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_shard.json")
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_ROOT = os.path.join(os.path.dirname(__file__), "..")

GATE_DEVICES = 4
GATE_MIN_SPEEDUP = 2.0
EVAL_POINTS = 1 << 20           # per evaluator call; device-count multiple
EVAL_REPS = 5
SWEEP_CHUNK = 200_000

_ARM = """
import json, time
import numpy as np
import jax
from repro.core.dse import (_device_tables, _flat_point_evaluator,
                            _model_scalars, _prepare_axes, grid_sweep)
from repro.core.perfmodel import AccelWorkload, SoCPerfModel

n_dev = {n_dev}
assert len(jax.devices()) >= n_dev, (n_dev, jax.devices())
model = SoCPerfModel()
wls = (AccelWorkload("dfadd", 9.22, 0.9),
       AccelWorkload("dfmul", 8.70, 1.1),
       AccelWorkload("dfsin", 0.33, 60.0))

# --- device-side evaluator, the first P points of a fixed space ---
P = {points}
lay, axes, vals = _prepare_axes(
    model, wls, (1, 2, 4), (0.2, 0.4, 0.6, 0.8, 1.0),
    (0.25, 0.5, 0.75, 1.0), (0.5, 1.0), ((1, 1), (3, 3), (0, 2), (2, 2)),
    "independent")
sizes = np.asarray([len(v) for _, v in axes], dtype=np.int32)
args = (P, np.zeros_like(sizes), sizes, _device_tables(model, lay, vals))
ev = _flat_point_evaluator(n_dev, *_model_scalars(model, wls, 2),
                           independent=True)
out = ev(*args)          # compile + warm
for o in out:
    o.block_until_ready()
best = float("inf")
for _ in range({reps}):
    t0 = time.perf_counter()
    out = ev(*args)
    for o in out:
        o.block_until_ready()
    best = min(best, time.perf_counter() - t0)

# --- end-to-end chunked sharded sweep ---
kw = dict(ks=(1, 2, 4), acc_rates=(0.2, 0.4, 0.6, 0.8, 1.0),
          noc_rates=(0.25, 0.5, 0.75, 1.0), tg_rates=(0.5, 1.0), n_tg=2,
          positions=((1, 1), (3, 3), (0, 2)),
          island_rates="independent", chunk_points={chunk})
grid_sweep(model, wls, devices=n_dev, **kw)      # compile + warm
t0 = time.perf_counter()
res = grid_sweep(model, wls, devices=n_dev, **kw)
sweep_s = time.perf_counter() - t0
print(json.dumps({{"eval_seconds": best, "eval_points": P,
                   "sweep_seconds": sweep_s,
                   "sweep_points": int(res.n_points)}}))
"""


def _run_arm(n_dev: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count", "--ignored") + " "
        f"--xla_force_host_platform_device_count={n_dev}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(_SRC), os.path.abspath(_ROOT),
         env.get("PYTHONPATH", "")])
    code = _ARM.format(n_dev=n_dev, points=EVAL_POINTS, reps=EVAL_REPS,
                       chunk=SWEEP_CHUNK)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_shard():
    # the arms are child processes on virtual CPU devices; on an
    # accelerator this process already holds the chip and they could not
    # open it
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"bench_shard runs its arms on virtual CPU devices, not on "
            f"{backend!r}; for the multi-chip path run "
            f"`python chip_smoke.py --chips 4`")
    arms = {n: _run_arm(n) for n in (1, GATE_DEVICES)}
    eval_speedup = (arms[1]["eval_seconds"]
                    / max(arms[GATE_DEVICES]["eval_seconds"], 1e-12))
    sweep_speedup = (arms[1]["sweep_seconds"]
                     / max(arms[GATE_DEVICES]["sweep_seconds"], 1e-12))
    cores = os.cpu_count() or 1
    enforced = cores >= GATE_DEVICES
    gate = {"devices": GATE_DEVICES, "min_speedup": GATE_MIN_SPEEDUP,
            "cpu_cores": cores, "enforced": enforced,
            "eval_speedup": eval_speedup, "sweep_speedup": sweep_speedup,
            "pass": (not enforced) or eval_speedup >= GATE_MIN_SPEEDUP}

    from benchmarks.run import append_bench_row
    append_bench_row(BENCH_JSON, {
        "eval_points": EVAL_POINTS, "sweep_chunk_points": SWEEP_CHUNK,
        "arms": {str(k): v for k, v in arms.items()},
        "gate": gate,
    })

    rows = [("shard_eval_1dev", arms[1]["eval_seconds"] * 1e6,
             f"P={EVAL_POINTS} flat-point evaluator, 1 device"),
            (f"shard_eval_{GATE_DEVICES}dev",
             arms[GATE_DEVICES]["eval_seconds"] * 1e6,
             f"{eval_speedup:.2f}x vs 1 device "
             f"(gate {'>=%.1fx' % GATE_MIN_SPEEDUP if enforced else 'off'}"
             f" @ {cores} cores)"),
            (f"shard_sweep_{GATE_DEVICES}dev",
             arms[GATE_DEVICES]["sweep_seconds"] * 1e6,
             f"end-to-end chunked sweep {sweep_speedup:.2f}x vs 1 device "
             f"({arms[GATE_DEVICES]['sweep_points']} points)")]
    if enforced:
        assert eval_speedup >= GATE_MIN_SPEEDUP, \
            f"sharded evaluator speedup {eval_speedup:.2f}x < " \
            f"{GATE_MIN_SPEEDUP}x at {GATE_DEVICES} devices ({cores} cores)"
    return rows


def run():
    return bench_shard()


if __name__ == "__main__":
    # direct execution puts benchmarks/ (not the repo root) on sys.path
    root = os.path.abspath(_ROOT)
    if root not in sys.path:
        sys.path.insert(0, root)
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
