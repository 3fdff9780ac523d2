"""Designs per ranking whose p50/p99 the device could not certify and
the host recomputed: the program's ``percentile_host_fallbacks`` counter
over the window's rankings.  A program without the counter (one that
computes every percentile on the host) reads nothing."""
from perfbench import spans

UNIT = "designs"
LAYER = "co-sim driver"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "program_counter"
COUNTER = "percentile_host_fallbacks"


def read(ctx):
    jobs = spans.window(ctx, "closed_loop_score")
    if jobs is None or not any(COUNTER in job for job in jobs):
        return None
    return spans.counted(jobs, COUNTER) / len(jobs)
