"""Tier-3: hourly cluster operating-point selector (paper Sect. 3.1, Eq. 3).

Grid search over the 2-D space (mean operating fraction mu in {0.4..0.9},
FR reserve band rho in {0.0..0.3}) maximising

    J(mu, rho) = 0.55 * Q_FFR(mu, rho) + 0.45 * CFE(mu, rho)
                 [+ w_rev * R(mu, rho)   when price-aware]
                 [+ w_tok * G(mu, rho)   when workload-aware]

Q_FFR is the relative FR-provision quality *at the facility meter* -- this
is what motivates the PUE correction: a CI-only controller evaluates the
band at the board and under-delivers at the meter when the marginal PUE is
below the static design PUE (floors bind as load sheds).

CFE uses the hourly greenness of the CI forecast: running high mu in
low-CI windows raises the day's Carbon-Free Energy share.

R is the settlement-revenue feedback from the reserve market (the E9
loop closure): expected capacity revenue of the committed band minus the
expected non-delivery clawback, priced with the SAME activation physics
``settle_reserve`` applies after the fact (:func:`revenue_score`).  A
price-aware selector avoids (mu, rho) cells whose governor-limited
delivery time or meter shortfall would forfeit the revenue.

The grid search itself is compiled ONCE at module level
(:func:`select_operating_points`); every :class:`Tier3Selector` instance
dispatches into the same jitted callable, so constructing selectors per
scenario (as the twin and engine do) never re-traces.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.plant as plant_lib
import repro.core.pue as pue_lib
import repro.grid.markets as markets
import repro.workload.model as workload_lib

MU_GRID = np.round(np.arange(0.4, 0.91, 0.1), 2)       # {0.4 .. 0.9}
RHO_GRID = np.round(np.arange(0.0, 0.31, 0.1), 2)      # {0.0 .. 0.3}
W_FFR, W_CFE = 0.55, 0.45
W_REV_DEFAULT = 0.25            # revenue-term weight when price-aware
# Shedding may not push the fleet below this fraction of design power.
# Capping alone bottoms out at ~0.33 TDP (100 W cap floor), but the duty
# shed preempts jobs entirely: an idled chip draws P_idle + min clocks
# ~53 W ~ 0.17 TDP, which is the physical fleet floor.
MIN_RESIDUAL_LOAD = 0.17
RHO_MAX = float(RHO_GRID[-1])

# reserve-settlement rules shared with repro.core.reserve (which re-exports
# them): delivery tolerance of the per-event verification, and the hours of
# capacity revenue at risk per failed event.
DELIVERY_TOL = 0.02
PENALTY_WINDOW_H = 24.0
EVENTS_PER_DAY_DEFAULT = 4.0    # Nordic activation-statistics order


class OperatingPoint(NamedTuple):
    mu: jax.Array    # mean operating fraction of design IT power
    rho: jax.Array   # committed FR reserve band (fraction of design IT)


def _farr(x) -> jax.Array:
    """float32 unless the input is already a wider float.

    Every f32 (and weakly-typed) input produces the exact pre-existing
    float32 graph; float64 inputs under ``jax.enable_x64(True)``
    keep full precision so the finite-difference gradcheck harness can
    compare against ``jax.grad`` below f32 roundoff.
    """
    x = jnp.asarray(x)
    return x.astype(jnp.result_type(x.dtype, jnp.float32))


def q_ffr(mu, rho, t_amb, *, pue_aware: bool, pue_design=pue_lib.PUE_DESIGN):
    """Relative FR-provision quality in [0, 1], evaluated at the meter.

    quality = (band size / max band) * delivery accuracy.

    The commitment is made in meter MW assuming the static design PUE
    (that is how European reserves are bid).  Actual delivery is the true
    facility-power delta of the IT shed.  A PUE-aware controller corrects
    its IT-side band so the meter delta matches the commitment (accuracy
    ~1); a PUE-blind one under-delivers when the marginal PUE < static.
    """
    mu = _farr(mu)
    rho = _farr(rho)
    feasible = (mu - rho) >= MIN_RESIDUAL_LOAD
    committed_meter = rho * pue_design  # static-PUE bid
    if pue_aware:
        # choose the IT band that truly delivers `committed_meter` at the
        # meter: invert F(mu) - F(mu - rho_it) = committed via 1 newton step
        gain = pue_lib.ffr_meter_gain(mu, rho, t_amb, pue_design=pue_design)
        rho_it = rho * pue_design / jnp.maximum(gain, 1e-3)
        rho_it = jnp.minimum(rho_it, mu - MIN_RESIDUAL_LOAD)
        delivered = pue_lib.ffr_meter_gain(
            mu, rho_it, t_amb, pue_design=pue_design) * rho_it
    else:
        delivered = pue_lib.ffr_meter_gain(
            mu, rho, t_amb, pue_design=pue_design) * rho
    accuracy = jnp.clip(
        delivered / jnp.maximum(committed_meter, 1e-6), 0.0, 1.0
    )
    # (rho/rho_max)^0.25: diminishing marginal FR-provision quality in band
    # size (the first committed MW pre-qualifies the site; extra MWs add
    # less).  This calibration reproduces the paper's Fig 4 operating
    # pattern: mu = 0.9 in green windows vs 0.4 overnight, ~20-30 % band.
    q = jnp.power(rho / RHO_MAX, 0.25) * accuracy
    return jnp.where(feasible, q, 0.0)


def cfe_score(mu, greenness) -> jax.Array:
    """Per-hour CFE proxy: energy-weighted alignment with low-CI windows.

    greenness in [0,1] is the normalised inverse CI of the hour.  Running
    high in green hours scores; running high in dirty hours anti-scores.
    """
    mu = _farr(mu)
    mu_n = mu / float(MU_GRID[-1])
    return greenness * mu_n + (1.0 - greenness) * (1.0 - mu_n)


# ---------------------------------------------------------------------------
# Activation physics (shared with the reserve replay: repro.core.reserve
# re-exports event_verdict so the scan and the Python reference agree
# bit-for-bit with what the selector optimises).
# ---------------------------------------------------------------------------


def event_verdict(mu, t_amb, rho, product_idx, pue_design,
                  pue_aware: bool = True) -> dict:
    """Physics of one activation at operating point ``mu`` (pure fn).

    Returns the armed IT-side band ``rho_it``, the governor-limited
    delivery time, and the meter-level delivered band per unit of design
    IT power.  Shared verbatim by the jnp scans (reserve replay, unified
    engine), the Python reference loop, and the Tier-3 revenue term so
    verdicts agree bit-for-bit.
    """
    mu = jnp.maximum(_farr(mu), 1e-3)
    rho = _farr(rho)
    if pue_aware:
        # invert the meter gain so the metered delta hits the static-PUE
        # commitment (q_ffr's correction, applied at dispatch time)
        gain = pue_lib.ffr_meter_gain(mu, rho, t_amb, pue_design=pue_design)
        rho_it = rho * pue_design / jnp.maximum(gain, 1e-3)
    else:
        rho_it = rho
    rho_it = jnp.clip(
        rho_it, 0.0, jnp.maximum(mu - MIN_RESIDUAL_LOAD, 0.0))
    # governor: P(t) = P_pre * exp(-GOV_SLEW * t) after the NVML window
    residual = jnp.maximum(mu - rho_it, 1e-3)
    t_full_ms = plant_lib.ACTUATE_DELAY_MS + (
        jnp.log(mu / residual) / plant_lib.GOV_SLEW)
    budget_ok = t_full_ms <= jnp.asarray(markets.BUDGET_MS)[product_idx]
    delivered_unit = pue_lib.ffr_meter_gain(
        mu, rho_it, t_amb, pue_design=pue_design) * rho_it
    committed_unit = rho * pue_design
    delivered_frac = jnp.where(
        committed_unit > 0.0, delivered_unit / committed_unit, 1.0)
    delivered_ok = delivered_frac >= 1.0 - DELIVERY_TOL
    return dict(rho_it=rho_it, t_full_ms=t_full_ms, budget_ok=budget_ok,
                delivered_unit=delivered_unit, delivered_frac=delivered_frac,
                delivered_ok=delivered_ok)


def revenue_score(mu, rho, t_amb, product_idx, *, pue_aware: bool,
                  pue_design=pue_lib.PUE_DESIGN,
                  events_per_day=EVENTS_PER_DAY_DEFAULT) -> jax.Array:
    """Expected reserve-settlement net revenue of a committed band, in
    units of the product's full-band capacity rate (so ~[-1, 1] after the
    clip below).

    Availability pays ``price * rho * PUE_design`` per hour; each expected
    activation (Poisson ``events_per_day``) puts PENALTY_WINDOW_H hours of
    that revenue at risk, forfeited in proportion to the meter shortfall
    plus in full on a delivery-time budget miss -- exactly the clawback
    ``settle_reserve`` applies after the fact, evaluated ex-ante with the
    same :func:`event_verdict` physics.  This is the Tier-3 price
    feedback: cells whose governor-limited ``t_full`` or PUE shortfall
    would forfeit revenue score negative and are avoided.
    """
    rho = _farr(rho)
    v = event_verdict(mu, t_amb, rho, product_idx, pue_design,
                      pue_aware=pue_aware)
    shortfall = jnp.clip(1.0 - v["delivered_frac"], 0.0, 1.0)
    hard_miss = 1.0 - v["budget_ok"].astype(jnp.float32)
    ev_per_h = _farr(events_per_day) / 24.0
    at_risk = ev_per_h * PENALTY_WINDOW_H * (shortfall + hard_miss)
    net = (rho / RHO_MAX) * (1.0 - at_risk)
    return jnp.clip(net, -1.0, 1.0)


def throughput_score(mu, rho, clock_w, product_idx, *,
                     events_per_day=EVENTS_PER_DAY_DEFAULT,
                     ckpt_cost_s=0.0) -> jax.Array:
    """Expected training-throughput retention of (mu, rho), in [0, 1].

    Tokens earned per hour relative to running flat-out at the top of
    the mu grid, through the SAME DVFS/duty-cycle curve
    (:func:`repro.workload.model.throughput_frac`) the engine tick
    accumulates and the live trainer actuates.  Three effects:

      * running at mu derates throughput to g(mu) (the DVFS curve),
      * each expected activation (Poisson ``events_per_day``) sheds to
        the residual ``mu - rho`` for the product's sustain window,
      * each activation also charges ``ckpt_cost_s`` of checkpoint+
        restore dead time (``repro.workload.ckpt_cost``) at zero
        throughput -- holding a band is not free even if the shed
        itself were.

    This is the workload half of J(mu, rho): weighted in, it pushes the
    selector toward higher mu and smaller committed bands exactly when
    the tokens forfeited outweigh the reserve revenue.
    """
    mu = _farr(mu)
    rho = _farr(rho)
    g_run = workload_lib.throughput_frac(clock_w, mu)
    resid = jnp.maximum(mu - rho, MIN_RESIDUAL_LOAD)
    g_shed = workload_lib.throughput_frac(clock_w, resid)
    ev_per_h = _farr(events_per_day) / 24.0
    dur_s = jnp.asarray(markets.MIN_DURATION_S)[product_idx]
    has_band = (rho > 0.0).astype(jnp.float32)
    shed_frac = jnp.clip(ev_per_h * dur_s / 3600.0, 0.0, 1.0) * has_band
    dead_frac = jnp.clip(
        ev_per_h * _farr(ckpt_cost_s) / 3600.0,
        0.0, 1.0) * has_band
    dead_frac = jnp.minimum(dead_frac, 1.0 - shed_frac)
    tokens = (1.0 - shed_frac - dead_frac) * g_run + shed_frac * g_shed
    g_max = workload_lib.throughput_frac(clock_w, float(MU_GRID[-1]))
    return tokens / jnp.maximum(g_max, 1e-6)


# ---------------------------------------------------------------------------
# The grid search, compiled once at module level.
# ---------------------------------------------------------------------------

# how many times the selection objective has been traced, keyed by input
# shape -- the regression test pins that a second same-shape call (or a
# second Selector instance) dispatches into the compile cache.
SELECT_TRACE_COUNT = {"n": 0}


def grid_candidates(rho_fixed=0.0, *, fix_rho: bool = False):
    """The selector's candidate mesh: (MU, RHO) of shape (6, R).

    Shared by the grid search below and by the differentiable bidder
    (``repro.optim.bidding``), whose grid-initialised argmax must be
    bit-identical to :func:`select_operating_points`.
    """
    mus = jnp.asarray(MU_GRID, jnp.float32)
    rhos = (jnp.reshape(jnp.asarray(rho_fixed, jnp.float32), (1,))
            if fix_rho else jnp.asarray(RHO_GRID, jnp.float32))
    return jnp.meshgrid(mus, rhos, indexing="ij")


def point_objective(mu, rho, greenness, t_amb, weights, product_idx,
                    events_per_day, clock_w, ckpt_cost_s, *,
                    pue_aware: bool, use_revenue: bool, use_workload: bool,
                    pue_design=pue_lib.PUE_DESIGN, price_rel=None):
    """The hourly selection objective J(mu, rho) at arbitrary points.

    Exactly the term order the grid search compiles -- q/cfe always,
    revenue and throughput gated by their static flags -- so any caller
    evaluating grid candidates through this function reproduces
    ``select_operating_points`` bit-for-bit.  ``price_rel`` (the bidder's
    capacity-price realisation relative to nominal) scales the revenue
    term; ``None`` omits the multiply entirely, keeping the legacy graph.
    """
    q = q_ffr(mu, rho, t_amb, pue_aware=pue_aware, pue_design=pue_design)
    J = weights[0] * q + weights[1] * cfe_score(mu, greenness)
    if use_revenue:
        rev = revenue_score(
            mu, rho, t_amb, product_idx, pue_aware=pue_aware,
            pue_design=pue_design, events_per_day=events_per_day)
        if price_rel is not None:
            rev = price_rel * rev
        J = J + weights[2] * rev
    if use_workload:
        J = J + weights[3] * throughput_score(
            mu, rho, clock_w, product_idx,
            events_per_day=events_per_day, ckpt_cost_s=ckpt_cost_s)
    return J


def _select_impl(greenness, t_amb, weights, pue_design, product_idx,
                 events_per_day, rho_fixed, clock_w, ckpt_cost_s, *,
                 pue_aware: bool, use_revenue: bool, fix_rho: bool,
                 use_workload: bool):
    """Vectorised (B,)-hour grid search.  Traced once per (shape, static)
    combination; all scalar knobs (weights, pue_design, product, rho,
    clock_w, ckpt cost) are traced operands so selector instances share
    the compile cache."""
    SELECT_TRACE_COUNT["n"] += 1
    MU, RHO = grid_candidates(rho_fixed, fix_rho=fix_rho)   # (6, R)
    g = greenness[:, None, None]
    ta = t_amb[:, None, None]
    J = point_objective(
        MU[None], RHO[None], g, ta, weights, product_idx, events_per_day,
        clock_w, ckpt_cost_s, pue_aware=pue_aware, use_revenue=use_revenue,
        use_workload=use_workload, pue_design=pue_design)
    flat = J.reshape(J.shape[0], -1)
    idx = jnp.argmax(flat, axis=-1)
    return MU.reshape(-1)[idx], RHO.reshape(-1)[idx]


_select_jit = jax.jit(
    _select_impl,
    static_argnames=("pue_aware", "use_revenue", "fix_rho", "use_workload"))


def _pad_weights(weights) -> jax.Array:
    """(w_ffr, w_cfe[, w_rev[, w_tok]]) -> a length-4 weight vector.

    Callers predating the workload term pass 3 weights; they get w_tok=0,
    which (with ``use_workload=False``) leaves the traced graph and the
    selection bit-identical to the pre-workload selector.
    """
    w = jnp.asarray(weights, jnp.float32).reshape(-1)
    if w.shape[0] > 4:
        raise ValueError(f"expected at most 4 selection weights, "
                         f"got {w.shape[0]}")
    if w.shape[0] < 4:
        w = jnp.concatenate([w, jnp.zeros((4 - w.shape[0],), jnp.float32)])
    return w


def select_operating_points(greenness, t_amb, *, pue_aware: bool,
                            pue_design=pue_lib.PUE_DESIGN,
                            weights=(W_FFR, W_CFE, 0.0),
                            product_idx=0,
                            events_per_day=EVENTS_PER_DAY_DEFAULT,
                            rho_fixed=0.0,
                            clock_w=None,
                            ckpt_cost_s=workload_lib.DEFAULT_GRID_CKPT_S,
                            use_revenue: bool = False,
                            fix_rho: bool = False,
                            use_workload: bool = False) -> OperatingPoint:
    """Functional hourly grid search: (B,) greenness/t_amb -> (B,) (mu, rho).

    ``fix_rho=True`` restricts the search to the (traced) committed band
    ``rho_fixed`` -- the unified engine's ``rho_mode="batch"`` path, where
    the band was sold ahead of time and only mu is free.
    ``use_workload=True`` adds ``weights[3] * throughput_score`` with the
    (traced) mix clock weight ``clock_w`` and per-event checkpoint cost;
    False keeps the traced graph identical to the pre-workload selector.
    Pure jnp and jit-compiled once at module level; safe to call inside
    an outer jit.
    """
    g = jnp.asarray(greenness, jnp.float32).reshape(-1)
    ta = jnp.broadcast_to(jnp.asarray(t_amb, jnp.float32).reshape(-1),
                          g.shape)
    if clock_w is None:
        clock_w = workload_lib.clock_weight("train")
    mu, rho = _select_jit(
        g, ta, _pad_weights(weights),
        jnp.asarray(pue_design, jnp.float32),
        jnp.asarray(product_idx, jnp.int32),
        jnp.asarray(events_per_day, jnp.float32),
        jnp.asarray(rho_fixed, jnp.float32),
        jnp.asarray(clock_w, jnp.float32),
        jnp.asarray(ckpt_cost_s, jnp.float32),
        pue_aware=pue_aware, use_revenue=use_revenue, fix_rho=fix_rho,
        use_workload=use_workload)
    return OperatingPoint(mu=mu, rho=rho)


def greenness_from_ci(ci, mask=None) -> jax.Array:
    """Normalised inverse CI over the (masked) forecast window."""
    ci = jnp.asarray(ci, jnp.float32)
    if mask is None:
        lo, hi = jnp.min(ci), jnp.max(ci)
    else:
        lo = jnp.min(jnp.where(mask > 0, ci, jnp.inf))
        hi = jnp.max(jnp.where(mask > 0, ci, -jnp.inf))
    return jnp.clip(1.0 - (ci - lo) / jnp.maximum(hi - lo, 1e-6), 0.0, 1.0)


@dataclasses.dataclass(frozen=True)
class Tier3Selector:
    """Hourly operating-point selection over a 24 h look-ahead window.

    ``w_rev > 0`` turns on the settlement-revenue feedback (price-aware
    operating points) for the FR product named by ``product``.  All
    instances dispatch into one module-level jitted grid search, so
    constructing a selector per scenario costs nothing.
    """

    pue_aware: bool = True
    pue_design: float = pue_lib.PUE_DESIGN
    w_ffr: float = W_FFR
    w_cfe: float = W_CFE
    w_rev: float = 0.0
    product: str = "FFR"
    events_per_day: float = EVENTS_PER_DAY_DEFAULT
    # workload term: weight of the throughput-retention score, the fleet's
    # workload mix, and the checkpoint dead time one activation charges
    w_tok: float = 0.0
    workload_mix: str = "train"
    ckpt_cost_s: float = workload_lib.DEFAULT_GRID_CKPT_S

    def objective(self, mu, rho, greenness, t_amb) -> jax.Array:
        q = q_ffr(mu, rho, t_amb, pue_aware=self.pue_aware,
                  pue_design=self.pue_design)
        c = cfe_score(mu, greenness)
        J = self.w_ffr * q + self.w_cfe * c
        if self.w_rev:
            J = J + self.w_rev * revenue_score(
                mu, rho, t_amb, markets.PRODUCT_ORDER.index(self.product),
                pue_aware=self.pue_aware, pue_design=self.pue_design,
                events_per_day=self.events_per_day)
        if self.w_tok:
            J = J + self.w_tok * throughput_score(
                mu, rho, workload_lib.clock_weight(self.workload_mix),
                markets.PRODUCT_ORDER.index(self.product),
                events_per_day=self.events_per_day,
                ckpt_cost_s=self.ckpt_cost_s)
        return J

    def select_hour(self, greenness, t_amb) -> OperatingPoint:
        """Grid search one hour.  greenness/t_amb are scalars (or batched)."""
        op = select_operating_points(
            greenness, t_amb, pue_aware=self.pue_aware,
            pue_design=self.pue_design,
            weights=(self.w_ffr, self.w_cfe, self.w_rev, self.w_tok),
            product_idx=markets.PRODUCT_ORDER.index(self.product),
            events_per_day=self.events_per_day,
            clock_w=workload_lib.clock_weight(self.workload_mix),
            ckpt_cost_s=self.ckpt_cost_s,
            use_revenue=bool(self.w_rev),
            use_workload=bool(self.w_tok))
        return OperatingPoint(mu=jnp.squeeze(op.mu), rho=jnp.squeeze(op.rho))

    def select_day(self, ci_24h, t_amb_24h) -> OperatingPoint:
        """Vectorised selection for a 24-entry forecast window."""
        green = greenness_from_ci(ci_24h)
        return self.select_hour(green, jnp.asarray(t_amb_24h, jnp.float32))


def cap_table(n_chips_per_host: int, host_design_w: float,
              cap_min: float, cap_max: float) -> np.ndarray:
    """Precomputed (mu x rho) -> per-chip cap lookup for the safety island.

    Entry [i, j] is the per-chip cap AFTER a full FFR activation at
    operating point (MU_GRID[i], RHO_GRID[j]): the cluster sheds rho of
    design power, so each chip caps at (mu - rho) * design / n_chips.
    Pure numpy; the island must never touch JAX on its hot path.
    """
    mu = MU_GRID[:, None]
    rho = RHO_GRID[None, :]
    residual = np.maximum(mu - rho, MIN_RESIDUAL_LOAD)
    per_chip = residual * host_design_w / n_chips_per_host
    return np.clip(per_chip, cap_min, cap_max).astype(np.float32)
