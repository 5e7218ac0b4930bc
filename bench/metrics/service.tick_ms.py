"""Mean of the service's own ``service.step_ms`` observations in the
window (ms): the batched tick on the host clock, from the call into
``SiteStore.step`` to the read-back of its shed and trigger flags."""
import numpy as np


def read(ctx):
    ms = ctx.result.get("tick_ms")
    return float(np.mean(ms)) if ms else None
