"""95th percentile of how late the load generator submitted each frame
due in the window (submit time minus due time), host clock, ms."""
import numpy as np


def read(ctx):
    lag = ctx.run.get("gen_lag_ms")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(lag, 95))
