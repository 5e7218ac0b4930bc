"""99th percentile of the service's ``service.step_ms`` observations in
the window (ms); the storm ticks set it."""
import numpy as np


def read(ctx):
    ms = ctx.result.get("tick_ms")
    return float(np.percentile(ms, 99)) if ms else None
