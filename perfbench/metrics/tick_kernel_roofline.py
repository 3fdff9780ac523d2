"""The Pallas tick kernel's share of its roofline: the least time of the
traced rankings' tick loops (perfbench.counts.tick_loop) over the device
time of the kernel's ops in the trace."""
from perfbench import counts, traces

UNIT = "%"
LAYER = "tick loop kernel"
MOVES = "cosim_design_ticks_per_s"
SOURCE = "device_trace"
KERNEL_OP = "tpu_custom_call"   # the kernel's op in the trace


def read(ctx):
    device_s = traces.op_seconds(ctx.trace, KERNEL_OP)
    if device_s <= 0 or not ctx.traced or ctx.peak is None:
        return None
    work = counts.tick_loop(**ctx.shape)
    least = counts.least_time(work, ctx.peak)["seconds"] * len(ctx.traced)
    return 100.0 * least / device_s
