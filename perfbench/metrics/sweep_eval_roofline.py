"""The sweep evaluator's share of its roofline: the least time for the
traced sweep's points (perfbench.counts.evaluator) over the device time
of the evaluator's program runs in the trace."""
from perfbench import counts, traces

UNIT = "%"
LAYER = "sweep evaluator"
MOVES = "sweep_points_per_s"
SOURCE = "device_trace"
EVALUATOR = "jit_fn"        # module of the sweep evaluator in the trace


def read(ctx):
    device_s = traces.module_seconds(ctx.trace, EVALUATOR)
    points = sum(j["work"] for j in ctx.traced)
    if device_s <= 0 or not points or ctx.peak is None:
        return None
    least = counts.least_time(counts.evaluator(points, ctx.shape["accels"]),
                              ctx.peak)
    return 100.0 * least["seconds"] / device_s
