"""Operations and bytes of the benchmark's kernels, counted from shapes.

Each function gives the least work an implementation has to do: the
floating-point operations of the model's equations and the bytes it has
to read and write in device memory, in float32.  The least time on a
chip is the larger of operations over peak FLOP/s and bytes over peak
bytes/s (:func:`least_time`).  Both the ``lax.scan`` tick loop and the
Pallas tick kernel are held to :func:`tick_loop`, so swapping one for the
other is judged on the same work.
"""
from __future__ import annotations

F32 = 4

# elementwise operations per point and accelerator in the sweep evaluator:
# throughput 12 (clamp, two divides, the wire term, the normalisation,
# the sum), power 7 (voltage, its square, the dynamic term, the sum),
# memory offer 3 (scale, clamp, sum)
EVAL_OPS_PER_ACCEL = 22
# per point, shared by all accelerators: the NoC saturation term 5, the
# mean power and the NoC power 8, energy 2, the memory-traffic min 7
EVAL_OPS_SHARED = 22


def evaluator(points: int, accels: int) -> dict:
    """One call of the sweep evaluator over ``points`` flat points:
    it reads (K, f_acc, hops) per accelerator plus f_noc and f_tg, and
    writes throughput, energy and memory traffic, each float32."""
    flops = points * (EVAL_OPS_SHARED + EVAL_OPS_PER_ACCEL * accels)
    nbytes = points * F32 * ((3 * accels + 2) + 3)
    return {"flops": float(flops), "bytes": float(nbytes)}


# per (design, tile) and tick: the queue update 2, service terms 12,
# capacity 5, serve/queue/busy 4, power 8, control-window sum 1
TICK_OPS_PER_TILE = 32
# with faults and deadlines: alive mask 3, drain 3, deadline drop 4
TICK_FAULT_OPS_PER_TILE = 10
# per (design, tile, link) and tick: the link loads (multiply, add) and
# the worst utilisation on each route (multiply, max)
TICK_OPS_PER_LINK = 4
# per (design, island) and control step: aggregation, PID, guard 20,
# plus 4 per ladder level to find the nearest one
CONTROL_OPS_PER_ISLAND = 20
CONTROL_OPS_PER_LEVEL = 4


def mesh_links(rows: int, cols: int) -> int:
    """Directed links of a rows x cols mesh."""
    return 2 * (rows * (cols - 1) + cols * (rows - 1))


def tick_loop(ticks: int, designs: int, tiles: int, links: int,
              islands: int, *, levels: int, control_interval: int,
              faults: bool, histories: int) -> dict:
    """``ticks`` ticks of ``designs`` stacked designs: it reads the shared
    (T, A) arrival trace and the per-design constants once and writes
    ``histories`` (T, B, A) float32 histories (admitted, served, and the
    work that left unserved when faults or deadlines drop it)."""
    T, B, A, L, I = ticks, designs, tiles, links, islands
    per_tick = B * A * (TICK_OPS_PER_TILE
                        + (TICK_FAULT_OPS_PER_TILE if faults else 0)) \
        + B * A * L * TICK_OPS_PER_LINK
    steps = T // control_interval if control_interval else 0
    control = steps * B * I * (CONTROL_OPS_PER_ISLAND
                               + CONTROL_OPS_PER_LEVEL * levels)
    nbytes = F32 * (T * A + histories * T * B * A
                    + B * (A * L + 8 * A + 4 * I))
    return {"flops": float(T * per_tick + control), "bytes": float(nbytes)}


def least_time(work: dict, peak: dict) -> dict:
    """Least seconds for ``work`` on a chip with ``peak`` rates, and
    which of the two bounds it."""
    t_ops = work["flops"] / peak["flops_per_s"]
    t_mem = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory"}
