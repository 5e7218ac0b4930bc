"""Driver of a replayed batch of a proportional product (Continental
Europe FCR): ``engine_rollout(reduce="summary")`` on one scenario grid,
called again and again for the window.

Set-up and window are those of ``bench/drivers/rollout.py``: the grid
from ``--seed``, stacked by ``build_scenario_batch``, one warm call,
then calls on the same batch until ``--seconds`` have passed.  The check
publishes the program's FCR counters (``fcr.*``) from every call's
outputs, replays every scenario through ``bench/reference_fcr.py`` and
compares every call's every scenario with it: the 4-hour block verdicts,
the required and delivered response each way, settlement, energy, the
twin and the RLS error.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference_fcr
from bench.drivers import rollout

setup = rollout.setup
window = rollout.window

RESPONSE = ("req_dn_mwh", "req_up_mwh", "dlv_dn_mwh", "dlv_up_mwh",
            "active_s", "up_s", "block_err_mw")
SETTLE = ("capacity_eur", "penalty_eur", "net_eur")
ENERGY = ("it_mwh", "fac_mwh", "shed_it_mwh", "sched_it_mwh",
          "sched_fac_mwh", "sched_co2_t", "sched_co2_it_t",
          "sched_cfe_fac_mwh", "sched_tokens_mtok", "tokens_mtok",
          "mean_mu", "committed_mw")
TWIN = ("tracking_err_mean", "chip_power_mean", "chip_power_p95")


def flatten(out: dict) -> dict:
    """A program rollout's outputs as host numpy."""
    return {k: np.asarray(v) for k, v in out.items()
            if k not in ("mu_h", "rho_h")}


def compare(got: list[dict], ref: dict) -> dict:
    """The numbers compared, over every call's every scenario."""
    blocks = np.zeros_like(ref["block_ok"], bool)
    response = settle = energy = twin = 0.0
    for g in got:
        blocks |= ((g["block_ok"] != ref["block_ok"])
                   | (g["block_valid"] != ref["block_valid"]))
        response = max([response] + [common.rel_gap(g[k], ref[k])
                                     for k in RESPONSE])
        settle = max([settle] + [common.rel_gap(g[k], ref[k]) for k in SETTLE])
        energy = max([energy] + [common.rel_gap(g[k], ref[k]) for k in ENERGY])
        twin = max([twin] + [common.rel_gap(g[k], ref[k]) for k in TWIN])
    return dict(block_off=int(blocks.sum()), response_gap=response,
                settle_gap=settle, energy_gap=energy, twin_gap=twin,
                rls_gap=max(common.median_gap(g["ar4_mae_norm"],
                                              ref["ar4_mae_norm"])
                            for g in got))


def aggregate(ref: dict, grid: list[dict], warmup_s: int) -> dict:
    """The numbers ``compare`` reads: this cell compares scenario by
    scenario, so its aggregate is the per-scenario outputs themselves
    (``bench/control.py`` aggregates before it compares)."""
    return ref


def reference_outputs(ctx, st, dt=jnp.float32) -> dict:
    return reference_fcr.run_scenarios(st["grid"], ctx.config["engine"],
                                       dt=dt)


def verify(ctx, st) -> dict:
    import repro.core.engine as eng

    outs = st.pop("outs")
    counts = [eng.publish_fcr_counters(o) for o in outs]
    got = [flatten(o) for o in outs]
    del outs
    st.pop("batch")
    ref = reference_outputs(ctx, st)
    active = sum(c["fcr.active_s"] for c in counts)
    ctx.result["notes"].append(
        f"fcr counters {counts[0]} per call, active share "
        f"{100.0 * active / (st['days'] * 86400.0 * len(counts)):.3f} %, "
        "gap by quantity "
        + str({k: max(common.rel_gap(g[k], ref[k]) for g in got)
               for k in RESPONSE + SETTLE + ENERGY + TWIN}))
    return common.checks(ctx.cell["name"], compare(got, ref))
