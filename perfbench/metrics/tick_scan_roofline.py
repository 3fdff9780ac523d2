"""The lax.scan tick loop's share of its roofline: the least time of the
traced rankings' tick loops (perfbench.counts.tick_loop, the same work
as the kernel's) over the device time of the scan's program runs."""
from perfbench import counts, traces

UNIT = "%"
LAYER = "tick loop scan"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "device_trace"
SCAN_MODULE = "jit_run_scan"    # module of the scan in the trace


def read(ctx):
    device_s = traces.module_seconds(ctx.trace, SCAN_MODULE)
    if device_s <= 0 or not ctx.traced or ctx.peak is None:
        return None
    work = counts.tick_loop(**ctx.shape)
    least = counts.least_time(work, ctx.peak)["seconds"] * len(ctx.traced)
    return 100.0 * least / device_s
