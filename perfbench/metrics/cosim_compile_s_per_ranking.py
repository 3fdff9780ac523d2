"""Seconds per ranking that JAX reports spending on tracing, lowering and
compiling (or loading from the compilation cache) inside the window:
the program builds its tick loop anew on every call."""

UNIT = "s"
LAYER = "co-sim driver"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "program_counter"


def read(ctx):
    jobs = [j for j in ctx.jobs if "engine_s" in j]
    if not jobs:
        return None
    return sum(j["compile_s"] for j in jobs) / len(jobs)
