"""Share of the traced window in which no program ran on the device (%),
averaged over the chips of the cell: 100 (1 - busy / window)."""


def read(ctx):
    p = ctx.profile
    if not p.get("window_s"):
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
