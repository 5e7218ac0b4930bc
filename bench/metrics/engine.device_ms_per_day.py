"""Device time of the cell's engine programs per scenario-day (ms).

Sums, over every chip, the ``XLA Modules`` time of the programs the
driver names (the rollout's ``_engine_seconds_jit``, the sweep's chunk
step) in the traced window, and divides by the scenario-days the window
completed."""


def read(ctx):
    names = ctx.result.get("programs", ())
    dev_s = sum(s for n, s in ctx.profile.get("program_s", {}).items()
                if n in names)
    if dev_s <= 0 or not ctx.result.get("days"):
        return None
    return dev_s * 1e3 / ctx.result["days"]
