"""Share of the window's in-horizon scenario-seconds in which the droop
answered a deviation outside the deadband (%): the program's
``fcr.active_s`` counter over the scenario-days the window completed.
The counters are published from the rollouts' outputs by the check, so
this reads them after it; a program without them reads nothing."""


def read(ctx):
    from repro.obs import trace

    active = trace.metrics.counters.get("fcr.active_s")
    days = ctx.result.get("days")
    if active is None or not days:
        return None
    return 100.0 * active / (days * 86400.0)
