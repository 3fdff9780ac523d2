"""Host time per ranking of building the stacked platform, the
controller and the engine: the program's ``cosim_build`` spans over the
window's rankings."""
from perfbench import spans

UNIT = "ms"
LAYER = "co-sim driver"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "program_span"
SPAN = "cosim_build"


def read(ctx):
    jobs = spans.window(ctx, "closed_loop_score")
    s = None if jobs is None else spans.seconds(jobs, SPAN)
    if s is None:
        return None
    return 1e3 * s / len(jobs)
