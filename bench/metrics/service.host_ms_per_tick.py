"""Host time per tick outside the batched step (ms): the window over the
ticks it ran, less the mean ``service.step_ms``.  It holds the trigger
ingest, the frequency feed and the service's resolution loops."""
import numpy as np


def read(ctx):
    r = ctx.result
    if not r.get("ticks") or not r.get("tick_ms"):
        return None
    return r["elapsed_s"] * 1e3 / r["ticks"] - float(np.mean(r["tick_ms"]))
