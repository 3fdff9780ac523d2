"""Tick loops built per ranking: the program's ``tick_loop_builds``
counter (one per jitted scan or ``pallas_call`` it builds) over the
window's rankings."""
from perfbench import spans

UNIT = "builds"
LAYER = "co-sim driver"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "program_counter"
COUNTER = "tick_loop_builds"


def read(ctx):
    jobs = spans.window(ctx, "closed_loop_score")
    if jobs is None:
        return None
    return spans.counted(jobs, COUNTER) / len(jobs)
