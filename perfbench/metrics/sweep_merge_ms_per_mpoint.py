"""Host time of the sweep outside chunk evaluation per million points:
the wall time of the window's ``grid_sweep`` calls less their
``sweep_chunk`` spans (set-up of the axes, the prefilter, the Pareto
and top-k merges, the survivor store)."""

UNIT = "ms/Mpoint"
LAYER = "sweep driver"
MOVES = "sweep_points_per_s"
SOURCE = "program_span"


def read(ctx):
    points = sum(j["work"] for j in ctx.jobs)
    chunk_s = sum(j.get("chunk_s", 0.0) for j in ctx.jobs)
    if not points or not chunk_s:
        return None
    wall = sum(j["wall_s"] for j in ctx.jobs)
    return (wall - chunk_s) / points * 1e9
