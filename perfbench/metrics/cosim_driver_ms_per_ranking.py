"""Host time of a ranking outside the tick loop: the wall time of each
``closed_loop_score`` call in the window less the program's
``BatchSimResult.elapsed_wall_s`` (platform and engine build, percentile
reconstruction, ranking), averaged over the window's rankings."""

UNIT = "ms"
LAYER = "co-sim driver"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "program_span"


def read(ctx):
    jobs = [j for j in ctx.jobs if "engine_s" in j]
    if not jobs:
        return None
    return 1e3 * sum(j["wall_s"] - j["engine_s"] for j in jobs) / len(jobs)
