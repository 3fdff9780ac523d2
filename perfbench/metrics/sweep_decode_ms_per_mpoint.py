"""Host time of the sweep's chunk decode per million points: the
program's ``sweep_decode`` spans over the window's sweeps (the 10-axis
``unravel_index``, the gathers, ``hop_counts``, the area sum, the
validity mask and ``np.stack``)."""
from perfbench import spans

UNIT = "ms/Mpoint"
LAYER = "sweep driver"
MOVES = "sweep_points_per_s"
SOURCE = "program_span"
SPAN = "sweep_decode"


def read(ctx):
    jobs = spans.window(ctx, "grid_sweep")
    points = sum(j["work"] for j in ctx.jobs)
    s = None if jobs is None else spans.seconds(jobs, SPAN)
    if s is None or not points:
        return None
    return s / points * 1e9
