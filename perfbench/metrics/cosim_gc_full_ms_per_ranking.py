"""Time per ranking spent in full (generation-2) garbage collections:
the program's ``gc_full`` spans inside the window's rankings."""
from perfbench import spans

UNIT = "ms"
LAYER = "co-sim driver"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "program_span"


def read(ctx):
    jobs = spans.window(ctx, "closed_loop_score")
    if jobs is None:
        return None
    return 1e3 * (spans.seconds(jobs, spans.GC_FULL) or 0.0) / len(jobs)
