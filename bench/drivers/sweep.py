"""Driver of a streamed fleet sweep: ``engine_sweep`` over one scenario
grid in fixed-size chunks, called again and again for the window.

``seconds_tier`` false sweeps the hourly tiers only (Tier-3 search and
schedule accounting); true adds the 1 Hz seconds scan.  ``mesh`` "auto"
shards every chunk over all chips of the machine, with one aggregate
lane per chip merged on the host at the end of each sweep; with
``--trace 1`` a sweep covers the first ``trace_specs`` scenarios (all,
where null).  Set-up warms
the chunk step with a sweep of one full chunk and, where the grid leaves
a partial last chunk, one of that size.  The check aggregates the plain
reference's per-scenario outputs, computed on the cell's chips, and
compares every sweep's fleet numbers with it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference

COUNTS = ("n_scenarios", "hours", "scenario_days", "seconds", "n_events",
          "active_s")
COMPLIANCE = ("n_compliant", "compliance", "compliance_sched")
TWIN = ("tracking_err_mean", "chip_power_mean", "chip_power_p95",
        "thr_mean")
# the fleet's mean RLS error is not compared: the float32 recursion can
# run away in a single scenario on either side (see bench/common.py
# median_gap), and one runaway scenario moves a fleet mean anywhere
NOT_COMPARED = ("ar4_mae_norm",)
SCHED_SUMS = ("sched_it_mwh", "sched_fac_mwh", "sched_co2_t",
              "sched_co2_it_t", "sched_cfe_fac_mwh", "sched_tokens_mtok")
SECONDS_SUMS = ("it_mwh", "fac_mwh", "shed_it_mwh", "active_s",
                "capacity_eur", "penalty_eur", "net_eur", "n_events",
                "n_compliant", "tokens_mtok", "tokens_ckpt_mtok",
                "tokens_lost_mtok")


def _sweep(ctx, st, specs):
    import repro.core.engine as eng

    return eng.engine_sweep(st["cfg"], specs, chunk_size=ctx.traffic["chunk"],
                            mesh=ctx.traffic["mesh"],
                            h_max=ctx.traffic["horizon_h"])


def setup(ctx) -> dict:
    grid = common.scenario_grid(ctx.traffic, ctx.seed)
    st = dict(grid=grid, specs=common.to_specs(grid),
              cfg=common.engine_config(
                  ctx.config, with_seconds=ctx.traffic["seconds_tier"]))
    chunk = ctx.traffic["chunk"]
    _sweep(ctx, st, st["specs"][:chunk])
    if len(grid) % chunk and len(grid) > chunk:
        _sweep(ctx, st, st["specs"][:chunk + len(grid) % chunk])
    return st


def window(ctx, st) -> dict:
    # a traced sweep of the seconds tier records every op of every tick
    # on every chip: ``trace_specs`` keeps the trace to its first chunks
    specs = st["specs"]
    if ctx.trace and ctx.traffic["trace_specs"]:
        specs = specs[:ctx.traffic["trace_specs"]]
    st["traced"] = len(specs)
    outs, t0 = [], time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            outs.append(_sweep(ctx, st, specs))
        elapsed = time.perf_counter() - t0
        if (len(outs) >= ctx.traffic["trace_calls"] if ctx.trace
                else elapsed >= ctx.seconds):
            break
    st["outs"] = outs
    days = sum(o["scenario_days"] for o in outs)
    return dict(elapsed_s=elapsed, attempted=len(outs), failed=0, days=days,
                notes=[],
                programs=("_sweep_step_jit", "run"),
                e2e=dict(scenario_days_per_s=days / elapsed))


def aggregate(ref: dict, grid: list[dict], warmup_s: int) -> dict:
    """The fleet numbers of per-scenario reference outputs (float64),
    by the sweep's definitions: sums, hour- and second-weighted means,
    per-event means over the fleet's events."""
    hours = np.asarray([d["horizon_h"] for d in grid], np.float64)
    hv = np.maximum(hours, 1.0)
    f = {k: np.asarray(v, np.float64) for k, v in ref.items()}
    H = max(hours.sum(), 1.0)
    out = dict(n_scenarios=float(len(grid)), hours=hours.sum(),
               scenario_days=hours.sum() / 24.0,
               mean_mu=np.sum(f["mean_mu"] * hv) / H,
               mean_rho=np.sum(f["mean_rho"] * hv) / H,
               cfe_mu=np.sum(f["cfe_mu"]) / H)
    out.update({k: np.sum(f[k]) for k in SCHED_SUMS})
    if "it_mwh" not in f:
        return out
    n_s = hours * 3600.0
    nw = np.maximum(n_s - warmup_s, 1.0)
    sec = max(n_s.sum(), 1.0)
    warm = max(np.maximum(n_s - warmup_s, 0.0).sum(), 1.0)
    valid = f["ev_valid"] > 0
    n_ev = max(np.sum(f["n_events"]), 1.0)
    out.update({k: np.sum(f[k]) for k in SECONDS_SUMS})
    out.update(
        seconds=n_s.sum(),
        ar4_mae_norm=np.sum(f["ar4_mae_norm"] * nw) / warm,
        tracking_err_mean=np.sum(f["tracking_err_mean"] * nw) / warm,
        chip_power_mean=np.sum(f["chip_power_mean"] * n_s) / sec,
        chip_power_p95=np.sum(f["chip_power_p95"] * n_s) / sec,
        thr_mean=np.sum(f["thr_mean"] * n_s) / sec,
        committed_mw=np.sum(f["committed_mw"] * hv) / H,
        compliance=np.sum(f["n_compliant"]) / n_ev,
        compliance_sched=np.sum(f["n_compliant_sched"]) / n_ev,
        delivered_frac_mean=np.sum(f["ev_delivered_frac"][valid]) / n_ev,
        resp_ms_mean=np.sum(f["ev_t_full_ms"][valid]) / n_ev,
        resp_ms_max=float(np.max(f["ev_t_full_ms"][valid], initial=0.0)),
        budget_ok_frac=np.sum(f["ev_budget_ok"][valid]) / n_ev,
        sustain_ok_frac=np.sum(f["ev_sustain_ok"][valid]) / n_ev,
        delivered_ok_frac=np.sum(f["ev_delivered_ok"][valid]) / n_ev)
    return out


def compare(got: list[dict], want: dict) -> dict:
    counts = [k for k in COUNTS if k in want]
    compl = [k for k in COMPLIANCE if k in want]
    twin = [k for k in TWIN if k in want]
    rest = [k for k in want
            if k not in counts + compl + twin + list(NOT_COMPARED)]
    count_off = compliance_off = 0
    agg = twin_gap = 0.0
    for g in got:
        count_off += sum(int(g[k] != want[k]) for k in counts)
        if compl:
            n_ev = max(want["n_events"], 1.0)
            compliance_off += int(round(
                abs(g["n_compliant"] - want["n_compliant"])
                + n_ev * abs(g["compliance_sched"]
                             - want["compliance_sched"])))
        agg = max([agg] + [abs(g[k] - want[k]) / max(abs(want[k]), 1e-6)
                           for k in rest])
        twin_gap = max([twin_gap] + [abs(g[k] - want[k]) / max(abs(want[k]),
                                                               1e-6)
                                     for k in twin])
    out = dict(count_off=count_off, agg_gap=float(agg))
    if compl:
        out.update(compliance_off=compliance_off, twin_gap=float(twin_gap))
    return out


def reference_outputs(ctx, st, dt=jnp.float32) -> dict:
    engine = dict(ctx.config["engine"],
                  with_seconds=ctx.traffic["seconds_tier"])
    block = 512 if ctx.traffic["seconds_tier"] else 8192
    return reference.run_scenarios(st["grid"], engine, block=block, dt=dt,
                                   devices=ctx.devices)


def verify(ctx, st) -> dict:
    got = st.pop("outs")
    st["grid"] = st["grid"][:st["traced"]]
    want = aggregate(reference_outputs(ctx, st), st["grid"],
                     ctx.config["engine"]["warmup_s"])
    ctx.result["notes"].append("gap by quantity " + str(
        {k: max(abs(g[k] - w) / max(abs(w), 1e-6) for g in got)
         for k, w in want.items()}))
    return common.checks(ctx.cell["name"], compare(got, want))
