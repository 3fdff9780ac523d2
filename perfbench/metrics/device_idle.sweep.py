"""Share of the traced sweep in which no op ran on the device (averaged
over the chips)."""

UNIT = "%"
LAYER = "device"
MOVES = "sweep_points_per_s"
SOURCE = "device_trace"


def read(ctx):
    w = ctx.trace["window_s"]
    if w <= 0 or ctx.trace["devices"] == 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / w)
