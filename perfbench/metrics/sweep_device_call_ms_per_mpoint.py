"""Host time of the sweep evaluator's call per million points: the
program's ``sweep_device_call`` spans over the window's sweeps (the
conversion to float32 and the transfer, the device run, the fetch and
the casts back to float64)."""
from perfbench import spans

UNIT = "ms/Mpoint"
LAYER = "sweep evaluator"
MOVES = "sweep_points_per_s"
SOURCE = "program_span"
SPAN = "sweep_device_call"


def read(ctx):
    jobs = spans.window(ctx, "grid_sweep")
    points = sum(j["work"] for j in ctx.jobs)
    s = None if jobs is None else spans.seconds(jobs, SPAN)
    if s is None or not points:
        return None
    return s / points * 1e9
