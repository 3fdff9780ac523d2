"""Host time of the sweep's running merge per million points: the
program's ``sweep_front`` spans over the window's sweeps (each chunk's
valid rows through the prefilter, the Pareto scan, the front merge and
the top-k updates)."""
from perfbench import spans

UNIT = "ms/Mpoint"
LAYER = "sweep driver"
MOVES = "sweep_points_per_s"
SOURCE = "program_span"
SPAN = "sweep_front"


def read(ctx):
    jobs = spans.window(ctx, "grid_sweep")
    points = sum(j["work"] for j in ctx.jobs)
    s = None if jobs is None else spans.seconds(jobs, SPAN)
    if s is None or not points:
        return None
    return s / points * 1e9
