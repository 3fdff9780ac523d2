"""Host time of the sweep's chunk evaluation per million points: the
program's ``sweep_chunk`` span over the window's sweeps, less the
evaluator's device time per point from the traced sweep (per chip: the
chips run their shards side by side)."""
from perfbench import traces

UNIT = "ms/Mpoint"
LAYER = "sweep driver"
MOVES = "sweep_points_per_s"
SOURCE = "program_span"
EVALUATOR = "jit_fn"        # module of the sweep evaluator in the trace


def read(ctx):
    points = sum(j["work"] for j in ctx.jobs)
    chunk_s = sum(j.get("chunk_s", 0.0) for j in ctx.jobs)
    if not points or not chunk_s:
        return None
    traced = sum(j["work"] for j in ctx.traced)
    device_s = traces.module_seconds(ctx.trace, EVALUATOR) \
        / max(ctx.trace["devices"], 1)
    per_point = device_s / traced if traced else 0.0
    return (chunk_s / points - per_point) * 1e9
