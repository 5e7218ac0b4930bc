"""Driver of a replayed batch: ``engine_rollout(reduce="summary")`` on one
scenario grid, called again and again for the window.

Set-up builds the grid from ``--seed``, stacks it with the program's own
batch builder and compiles the rollout with one warm call.  The window
calls the rollout on the same batch until ``--seconds`` have passed (with
``--trace 1``: ``trace_calls`` calls) and keeps every call's output.  The
check replays every scenario through the plain reference and compares
every call's every scenario with it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference

SETTLE = ("capacity_eur", "penalty_eur", "net_eur")
ENERGY = ("it_mwh", "fac_mwh", "shed_it_mwh", "sched_it_mwh", "sched_fac_mwh",
          "sched_co2_t", "sched_co2_it_t", "sched_cfe_fac_mwh",
          "sched_tokens_mtok", "tokens_mtok", "mean_mu", "committed_mw")
TWIN = ("tracking_err_mean", "chip_power_mean", "chip_power_p95")
# the rollout's device programs: keys, frequency synthesis, the scan
PROGRAMS = ("_scenario_keys_jit", "synthesize_frequency_batch",
            "_engine_seconds_jit")


def setup(ctx) -> dict:
    import repro.core.engine as eng
    from repro.grid.scenarios import build_scenario_batch

    grid = common.scenario_grid(ctx.traffic, ctx.seed)
    batch = build_scenario_batch(common.to_specs(grid))
    cfg = common.engine_config(ctx.config)
    jax.block_until_ready(eng.engine_rollout(cfg, batch, reduce="summary"))
    return dict(grid=grid, batch=batch, cfg=cfg,
                days=float(np.sum(np.asarray(batch.hours))) / 24.0)


def window(ctx, st) -> dict:
    import repro.core.engine as eng

    outs, t0 = [], time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            outs.append(jax.block_until_ready(
                eng.engine_rollout(st["cfg"], st["batch"], reduce="summary")))
        elapsed = time.perf_counter() - t0
        if (len(outs) >= ctx.traffic["trace_calls"] if ctx.trace
                else elapsed >= ctx.seconds):
            break
    st["outs"] = outs
    return dict(elapsed_s=elapsed, attempted=len(outs), failed=0, notes=[],
                days=st["days"] * len(outs), programs=PROGRAMS,
                e2e=dict(scenario_days_per_s=st["days"] * len(outs) / elapsed))


def flatten(out: dict) -> dict:
    """A program rollout's outputs under the reference's names."""
    host = jax.tree.map(np.asarray, out)
    flat = {k: v for k, v in host.items()
            if k not in ("events", "events_sched", "mu_h", "rho_h")}
    ev, evs = host["events"], host["events_sched"]
    flat.update(t_event_s=ev.t_event_s, ev_valid=ev.valid,
                ev_delivered_frac=ev.delivered_frac,
                ev_t_full_ms=ev.t_full_ms,
                n_compliant_sched=np.sum(evs.valid & evs.compliant, axis=-1))
    return flat


def compare(got: list[dict], ref: dict) -> dict:
    """The numbers compared, over every call's every scenario."""
    det = np.zeros(len(ref["n_events"]), bool)
    comp = np.zeros_like(det)
    settle = energy = twin = 0.0
    for g in got:
        det |= ((g["n_events"] != ref["n_events"])
                | (g["active_s"] != ref["active_s"])
                | np.any(g["t_event_s"] != ref["t_event_s"], axis=-1))
        comp |= ((g["n_compliant"] != ref["n_compliant"])
                 | (g["n_compliant_sched"] != ref["n_compliant_sched"]))
        settle = max([settle] + [common.rel_gap(g[k], ref[k]) for k in SETTLE])
        energy = max([energy] + [common.rel_gap(g[k], ref[k]) for k in ENERGY])
        twin = max([twin] + [common.rel_gap(g[k], ref[k]) for k in TWIN])
    return dict(detect_off=int(det.sum()), compliance_off=int(comp.sum()),
                settle_gap=settle, energy_gap=energy, twin_gap=twin,
                rls_gap=max(common.median_gap(g["ar4_mae_norm"],
                                              ref["ar4_mae_norm"])
                            for g in got))


def gaps_by_key(got: list[dict], ref: dict) -> dict:
    """Diagnostics: each quantity's widest gap, and how many scenarios
    hold a non-finite or runaway (over 10x the median) RLS error."""
    out = {k: max(common.rel_gap(g[k], ref[k]) for g in got)
           for k in SETTLE + ENERGY + TWIN + ("ar4_mae_norm",)}
    for side, x in (("program", got[0]["ar4_mae_norm"]),
                    ("reference", ref["ar4_mae_norm"])):
        x = np.asarray(x, np.float64)
        med = np.median(x[np.isfinite(x)])
        out[f"ar4_runaway_{side}"] = int(np.sum(~np.isfinite(x)
                                                | (x > 10 * med)))
    return out


def reference_outputs(ctx, st, dt=jnp.float32) -> dict:
    engine = dict(ctx.config["engine"], with_seconds=True)
    return reference.run_scenarios(st["grid"], engine, dt=dt)


def verify(ctx, st) -> dict:
    got = [flatten(o) for o in st.pop("outs")]
    st.pop("batch")
    ref = reference_outputs(ctx, st)
    ctx.result["notes"].append(f"gap by quantity {gaps_by_key(got, ref)}")
    return common.checks(ctx.cell["name"], compare(got, ref))
