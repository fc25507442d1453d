"""Share of the traced window in which no operation ran on the chip
(averaged over chips), live cells, %."""
import tracing


def read(ctx):
    if ctx.kind != "live" or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - tracing.busy_s(ctx.trace) / ctx.trace.window_s)
