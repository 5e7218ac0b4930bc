"""Instantaneous four-component PUE model (paper Eq. 4, Sect. 3.3).

    PUE(t, L, T_amb) = 1 + (P_chiller + P_pumps + P_air + P_misc) / P_IT

with L = P_IT / P_IT_design, affinity laws P_pumps ~ L^2 (floored at 20 %
for bypass flow) and P_air ~ L^3 (floored at 15 % for minimum
controllability), and a free-cooling fraction ramping linearly from 0 at
25 degC ambient to 1 at 12 degC wet-bulb.  Calibrated to the published
Marconi100 design point: PUE = 1.20 at full load (reference ambient).

All functions are jnp-vectorised over time/site AND over a leading scenario
axis: `load`, `t_amb`, and `pue_design` may each be scalars, (H,) traces,
or vmap-traced per-scenario values, so the batched sweep engine evaluates
the meter model for every (country x season x seed x level x design)
combination in one compiled call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PUE_DESIGN = 1.20        # Marconi100 design point at L = 1
T_FREECOOL_HI = 25.0     # degC ambient: f_fc = 0
T_FREECOOL_LO = 12.0     # degC wet-bulb: f_fc = 1
PUMP_FLOOR = 0.20        # bypass-flow floor (fraction of design pump power)
AIR_FLOOR = 0.15         # minimum-controllability floor
T_REF = 18.0             # degC reference ambient used for calibration

def _farr(x) -> jax.Array:
    """float32 unless the input is already a wider float (the x64
    gradcheck harness); f32 and weakly-typed inputs keep the exact
    pre-existing float32 graph."""
    x = jnp.asarray(x)
    return x.astype(jnp.result_type(x.dtype, jnp.float32))


# Design-point split of the (PUE-1) overhead into the four components.
# Chiller dominates on a chilled-water site; pumps/air/misc share the rest.
CHILLER_SHARE = 0.55
PUMP_SHARE = 0.18
AIR_SHARE = 0.15
MISC_SHARE = 0.12


def free_cooling_fraction(t_amb) -> jax.Array:
    """f_fc(T_amb): 0 at >=25 degC, 1 at <=12 degC, linear between."""
    t = _farr(t_amb)
    return jnp.clip((T_FREECOOL_HI - t) / (T_FREECOOL_HI - T_FREECOOL_LO),
                    0.0, 1.0)


def _overhead_design(pue_design=PUE_DESIGN) -> jax.Array:
    """Total facility overhead per watt of IT at the design point.

    Accepts a scalar, an array, or a traced per-scenario value (the E9
    design-sensitivity axis of the batched sweep).
    """
    return _farr(pue_design) - 1.0


def pue(load, t_amb, *, pue_design: float = PUE_DESIGN) -> jax.Array:
    """Instantaneous PUE.  load = P_IT / P_IT_design in (0, 1]; t_amb degC.

    Components (per watt of design IT power):
      chiller: ~ proportional to heat load, scaled down by free cooling
      pumps:   ~ L^2, floored at 20 %
      air:     ~ L^3, floored at 15 %
      misc:    constant (lighting, UPS losses, controls)
    PUE divides by the *actual* IT power L * P_design, which is what drives
    the overhead fraction UP as the controller sheds IT load.
    """
    L = jnp.clip(_farr(load), 1e-3, 1.0)
    oh = _overhead_design(pue_design)
    f_fc = free_cooling_fraction(t_amb)
    f_ref = free_cooling_fraction(T_REF)
    # part-load chiller COP degradation (IPLV-style: ~45 % worse specific
    # power at zero load; the effect Zhao's multi-chiller MPC [33] manages)
    cop_penalty = 1.0 + 0.45 * (1.0 - L)
    # calibration: at L=1, T_REF ambient, total overhead == oh exactly.
    chiller_scale = oh * CHILLER_SHARE / (1.0 - 0.85 * f_ref)
    p_chiller = chiller_scale * L * cop_penalty * (1.0 - 0.85 * f_fc)
    p_pumps = oh * PUMP_SHARE * jnp.maximum(L * L, PUMP_FLOOR)
    p_air = oh * AIR_SHARE * jnp.maximum(L * L * L, AIR_FLOOR)
    p_misc = oh * MISC_SHARE
    return 1.0 + (p_chiller + p_pumps + p_air + p_misc) / L


def facility_power(p_it, p_it_design, t_amb,
                   *, pue_design: float = PUE_DESIGN) -> jax.Array:
    """Metered facility power for an IT draw p_it (same units)."""
    L = p_it / p_it_design
    return p_it * pue(L, t_amb, pue_design=pue_design)


def ffr_meter_gain(mu, rho, t_amb, *, pue_design: float = PUE_DESIGN):
    """Meter-side FFR delivery per unit of committed IT-side band.

    A commitment to shed rho*P_design of IT power delivers

        [F(mu) - F(mu - rho)] / (rho * P_design)

    at the meter, where F is facility_power.  Because PUE rises as L falls
    (the L^2/L^3 floors bind), this is < 1: the under-delivery the paper
    quantifies as 4-7 pp.  Tier-3 uses this to evaluate Q_FFR at the meter.
    """
    rho = jnp.maximum(_farr(rho), 1e-6)
    hi = facility_power(mu, 1.0, t_amb, pue_design=pue_design)
    lo = facility_power(jnp.maximum(mu - rho, 0.02), 1.0, t_amb,
                        pue_design=pue_design)
    return (hi - lo) / rho


def meter_gain_up(mu, rho, t_amb, *, pue_design: float = PUE_DESIGN):
    """Meter-side delivery per unit of IT-side band *raised* from ``mu``:

        [F(mu + rho) - F(mu)] / (rho * P_design)

    the up-regulation side of :func:`ffr_meter_gain`.  A symmetric
    proportional product (FCR-CE) commits the same meter MW both ways, and
    the marginal PUE above ``mu`` differs from the one below it (the L^2 /
    L^3 terms grow with load), so each direction gets its own correction.
    """
    rho = jnp.maximum(_farr(rho), 1e-6)
    hi = facility_power(jnp.minimum(mu + rho, 1.0), 1.0, t_amb,
                        pue_design=pue_design)
    lo = facility_power(mu, 1.0, t_amb, pue_design=pue_design)
    return (hi - lo) / rho
