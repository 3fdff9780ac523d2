#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout; the cells are listed in
``BENCHMARK.json``.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared with the reference beside its limit); the same numbers end
standard error.  Without a TPU, with fewer chips than the cell needs, or
on a chip ``perfbench/peaks.json`` does not list, it exits with code 2
and prints no result.  JAX's compilation cache is kept in
``<checkout>/.jax_cache``.
"""
import os
import sys
import time

_T0 = time.perf_counter()

def _process_age() -> float:
    """Seconds since this process started (0 where /proc is missing)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    CACHE = os.path.join(ROOT, ".jax_cache")
    os.makedirs(CACHE, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    start = _T0 - _process_age()
    from repro.shard import enable_compile_cache
    enable_compile_cache()
    from perfbench import harness
    sys.exit(harness.main(root=ROOT, process_start=start))
