"""Seconds-tier reserve-market replay & settlement engine (E9).

The third tier of the codebase, between the millisecond safety island (E7)
and the hourly carbon dispatch (E8): replay a 1 Hz grid-frequency trace
against the plant model, detect per-product threshold crossings, verify
delivery compliance per event, and settle the committed band at the
facility meter.

Per-event compliance (paper Sect. 2 + Nordic FFR rules):

  * time-to-full-delivery: the armed shed goes through the firmware cap
    governor, a multiplicative slew of GOV_SLEW per ms after the
    ACTUATE_DELAY_MS write latency, so
    ``t_full = delay + ln(P_pre / P_post) / GOV_SLEW`` must clear the
    product's ``activation_budget_ms`` (the paper's 97.2 ms vs 700 ms),
  * sustain: the shed is held for ``min_duration_s`` from activation (an
    event too close to the horizon edge cannot complete its window),
  * meter-level delivery: the commitment is ``rho * design * PUE_design``
    MW at the meter; the true meter delta of an IT-side shed is smaller
    when the marginal PUE is below the static design PUE (the L^2/L^3
    floors bind), so a PUE-blind site under-delivers by 4-7 pp while the
    PUE-aware correction inflates the IT band to hit the metered number.

The replay itself is ONE ``lax.scan`` over seconds with an event-detection
state machine in the carry (armed / holding / released), fixed-size
per-event verdict buffers, and pure-jnp everything -- ``vmap`` runs the
whole :class:`repro.grid.scenarios.ScenarioBatch` in a single compiled
call.  ``reserve_replay_reference`` is the per-event Python loop the
benchmark races and the tests pin verdict parity against.

Scope note: threshold-crossing activation models the *event* products
(FFR, FCR-D), whose triggers sit far below the ~10 mHz baseline wander.
The slow restoration products (FCR at 49.98, aFRR/mFRR at 49.99) are
dispatched near-continuously by TSO setpoint in reality, and their
thresholds sit inside ordinary frequency noise -- replaying them through
this state machine detects wander crossings as activations and holds
each for the full ``min_duration_s``.  That is the correct reading of
the threshold semantics, but not a model of how those products are
called.

Proportional products (FCR-CE) have no events: every second the site
answers the signed activation :func:`droop_activation`, the engine sums
the required and delivered meter response per settlement block, and a
block that tracked badly forfeits its capacity payment
(:func:`block_verdicts`, :func:`block_clawback`).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.tier3 as tier3_lib
from repro.core.tier3 import event_verdict  # noqa: F401  (re-export: the
# activation physics moved next to the Tier-3 selector so the price-aware
# grid search and the replay verdicts share one function; this module keeps
# its historical name for the scan, the reference loop, and callers.)
from repro.grid import markets

E_MAX = 64                  # per-scenario event-buffer slots
# settlement rules live next to the selector that optimises against them
DELIVERY_TOL = tier3_lib.DELIVERY_TOL
PENALTY_WINDOW_H = tier3_lib.PENALTY_WINDOW_H

# product constant tables, indexable by a traced int32 product index
_PRODUCTS = [markets.FR_PRODUCTS[n] for n in markets.PRODUCT_ORDER]
_TRIGGER_HZ = markets.TRIGGER_HZ
_BUDGET_MS = markets.BUDGET_MS
_MIN_DURATION_S = markets.MIN_DURATION_S
_PRICE_EUR_MW_H = markets.CAPACITY_PRICE_EUR_MW_H


class ReserveEvents(NamedTuple):
    """Fixed-size per-event verdict buffers; all fields (..., E_MAX)."""

    t_event_s: jax.Array      # int32 activation second (-1 on empty slots)
    t_full_ms: jax.Array      # float32 trigger-to-full-delivery time
    sustain_s: jax.Array      # float32 achievable hold inside the horizon
    delivered_mw: jax.Array   # float32 meter-level delivered band
    delivered_frac: jax.Array  # float32 delivered / committed (meter MW)
    budget_ok: jax.Array      # bool t_full_ms <= activation_budget_ms
    sustain_ok: jax.Array     # bool full min_duration_s fits the horizon
    delivered_ok: jax.Array   # bool delivered_frac >= 1 - DELIVERY_TOL
    compliant: jax.Array      # bool all three
    valid: jax.Array          # bool slot holds a real event


_event_verdict_jit = jax.jit(event_verdict, static_argnames=("pue_aware",))


def detection_step(carry, below, in_hor, min_dur_i):
    """One 1 Hz tick of the two-word detection state machine.

    carry = (in_event: bool, hold: int32).  Returns the new carry plus the
    per-second (triggered, shedding) flags.  Factored out so the unified
    ``repro.core.engine`` scan runs the IDENTICAL semantics fused into the
    twin's tick -- event times match :func:`reserve_replay` exactly.
    """
    in_ev, hold = carry
    trig = ~in_ev & below & in_hor
    in_ev = in_ev | trig
    hold = jnp.where(trig, min_dur_i, hold)
    hold = jnp.where(in_ev, jnp.maximum(hold - 1, 0), hold)
    released = in_ev & (hold == 0) & ~below
    shed = in_ev & in_hor
    return (in_ev & ~released, hold), trig, shed


def detection_init():
    """Initial (in_event, hold) carry of the detection state machine."""
    return (jnp.asarray(False), jnp.asarray(0, jnp.int32))


def event_times(trig, e_max: int):
    """(T,) trigger flags -> (t_event (e_max,), valid (e_max,)).

    The k-th trigger second is the first index where the running trigger
    count reaches k+1, found by binary search on the cumsum (ascending,
    exactly the order a sequential writer would record; overflow slots
    land at T).  nonzero/top_k would sort the whole (T,) axis under vmap
    -- ~10x this cost on CPU.
    """
    T = trig.shape[-1]
    t_ev = jnp.searchsorted(
        jnp.cumsum(trig.astype(jnp.int32)),
        jnp.arange(1, e_max + 1)).astype(jnp.int32)
    return t_ev, t_ev < T


def assemble_events(v: dict, t_ev, valid, min_dur_f, valid_s,
                    design_mw) -> ReserveEvents:
    """Fixed-size verdict buffers from per-event physics ``v`` (each leaf
    (e_max,)-shaped, as returned by :func:`event_verdict` gathered at the
    event hours -- or, in the unified engine, evaluated at the twin's
    per-second IT power)."""
    sustain_s = jnp.minimum(min_dur_f, (valid_s - t_ev).astype(jnp.float32))
    sustain_ok = sustain_s >= min_dur_f
    compliant = v["budget_ok"] & sustain_ok & v["delivered_ok"]

    def gate(x, fill=0.0):
        return jnp.where(valid, x, fill)

    return ReserveEvents(
        t_event_s=gate(t_ev, -1),
        t_full_ms=gate(v["t_full_ms"]),
        sustain_s=gate(sustain_s),
        delivered_mw=gate(v["delivered_unit"] * design_mw),
        delivered_frac=gate(v["delivered_frac"]),
        budget_ok=gate(v["budget_ok"], False),
        sustain_ok=gate(sustain_ok, False),
        delivered_ok=gate(v["delivered_ok"], False),
        compliant=gate(compliant, False),
        valid=valid,
    )


def reserve_replay(freq, mu_h, t_amb_h, valid_s, product_idx, rho,
                   design_mw, pue_design, *, pue_aware: bool = True,
                   e_max: int = E_MAX, unroll: int = 8) -> dict:
    """Replay one scenario's 1 Hz frequency trace; detect + verify events.

    freq: (T,) Hz at 1 Hz;  mu_h/t_amb_h: (H,) hourly operating fraction /
    ambient;  valid_s: scalar count of real seconds (ragged horizons);
    product_idx/rho/design_mw/pue_design: scalars (may be traced).

    Detection state machine (identical in ``reserve_replay_reference``):
    a new event starts when frequency drops below the product trigger
    while released; the site then holds the shed for ``min_duration_s``
    and releases at the first second where the window is complete AND
    frequency has recovered above the trigger.  Crossings inside a held
    window do not re-trigger.

    Pure jnp, ONE ``lax.scan`` over seconds; vmappable over every argument.
    The scan carry holds only the two-word state machine (in-event flag +
    hold countdown) and emits per-second trigger/shed flags; the per-event
    verdict buffers are then gathered vectorised from the flags and the
    hoisted per-hour physics table (``jnp.nonzero(size=e_max)``), which
    keeps the scan body free of scatter writes -- the difference between
    this path beating the Python loop and losing to it by 50x on CPU.
    """
    freq = jnp.asarray(freq, jnp.float32)
    mu_h = jnp.asarray(mu_h, jnp.float32)
    t_amb_h = jnp.asarray(t_amb_h, jnp.float32)
    h_max = mu_h.shape[-1]
    valid_s = jnp.asarray(valid_s, jnp.int32)
    product_idx = jnp.asarray(product_idx, jnp.int32)
    rho = jnp.asarray(rho, jnp.float32)
    design_mw = jnp.asarray(design_mw, jnp.float32)

    trig_hz = jnp.asarray(_TRIGGER_HZ)[product_idx]
    min_dur_f = jnp.asarray(_MIN_DURATION_S)[product_idx]
    min_dur_i = min_dur_f.astype(jnp.int32)

    # per-hour activation physics, hoisted out of the scan: the verdict of
    # an event depends only on its trigger hour's (mu, T_amb), so the
    # post-scan extraction just gathers from these (H,) tables
    vh = event_verdict(mu_h, t_amb_h, rho, product_idx, pue_design,
                       pue_aware=pue_aware)

    # vectorised precompute: the scan body only carries the two-word state
    # machine; threshold compares and horizon gating are (T,) elementwise
    T = freq.shape[-1]
    below_t = freq < trig_hz
    in_hor_t = jnp.arange(T, dtype=jnp.int32) < valid_s

    def step(carry, xs):
        below, in_hor = xs
        carry, trig, shed = detection_step(carry, below, in_hor, min_dur_i)
        return carry, (trig, shed)

    _, (trig, shed) = jax.lax.scan(step, detection_init(),
                                   (below_t, in_hor_t), unroll=unroll)

    # vectorised per-event extraction (see event_times): the scan body
    # only carries the two-word state machine, keeping it free of scatter
    # writes -- the difference between this path beating the Python loop
    # and losing to it by 50x on CPU.
    t_ev, valid = event_times(trig, e_max)
    hour_ev = jnp.minimum(t_ev // 3600, h_max - 1)
    v = {k: x[hour_ev] for k, x in vh.items()}
    events = assemble_events(v, t_ev, valid, min_dur_f, valid_s, design_mw)
    hour_sec = jnp.minimum(jnp.arange(T, dtype=jnp.int32) // 3600, h_max - 1)
    shed_it_mwh = jnp.sum(
        jnp.where(shed, vh["rho_it"][hour_sec], 0.0)) * design_mw / 3600.0
    return dict(events=events, n_events=jnp.sum(valid).astype(jnp.int32),
                active_s=jnp.sum(shed).astype(jnp.int32),
                shed_it_mwh=shed_it_mwh)


@partial(jax.jit, static_argnames=("pue_aware", "e_max", "unroll"))
def reserve_replay_batch(freq, mu_h, t_amb_h, valid_s, product_idx, rho,
                         design_mw, pue_design, *, pue_aware: bool = True,
                         e_max: int = E_MAX, unroll: int = 8) -> dict:
    """The whole scenario batch as ONE jitted ``vmap(scan)``.

    Every argument carries a leading (N,) scenario axis ((N, T) freq,
    (N, H) hourly traces, (N,) scalars).  Returns dict leaves with a
    leading (N,) axis.
    """
    fn = partial(reserve_replay, pue_aware=pue_aware, e_max=e_max,
                 unroll=unroll)
    return jax.vmap(fn)(freq, mu_h, t_amb_h, valid_s, product_idx, rho,
                        design_mw, pue_design)


def event_clawback(events: ReserveEvents, at_risk) -> jax.Array:
    """Revenue forfeited over a verdict buffer: each valid event loses its
    ``at_risk`` revenue in proportion to the delivery shortfall plus in
    full on a budget/sustain failure (the European non-delivery clawback
    shape).  ``at_risk``: (..., E) or broadcastable.  THE one
    implementation of the clawback formula -- `settle_reserve`, the
    unified engine's hourly-band settlement, and (ex-ante)
    `tier3.revenue_score` all price the same rule.
    """
    shortfall = jnp.clip(1.0 - events.delivered_frac, 0.0, 1.0)
    hard_miss = (~(events.budget_ok & events.sustain_ok)).astype(jnp.float32)
    return jnp.sum(
        jnp.where(events.valid, at_risk * (shortfall + hard_miss), 0.0),
        axis=-1)


def settle_reserve(events: ReserveEvents, product_idx, rho, design_mw,
                   pue_design, hours) -> dict:
    """Capacity-revenue / penalty settlement of one committed band.

    Availability pays ``price * committed_MW`` per committed hour; each
    event puts PENALTY_WINDOW_H hours of that revenue at risk
    (see :func:`event_clawback`).  Pure jnp over any leading batch axes
    (event fields are (..., E)).
    """
    price = jnp.asarray(_PRICE_EUR_MW_H)[jnp.asarray(product_idx)]
    committed_mw = (jnp.asarray(rho, jnp.float32)
                    * jnp.asarray(design_mw, jnp.float32)
                    * jnp.asarray(pue_design, jnp.float32))
    capacity_eur = committed_mw * jnp.asarray(hours, jnp.float32) * price
    penalty_eur = event_clawback(
        events, (price * committed_mw * PENALTY_WINDOW_H)[..., None])
    return dict(
        committed_mw=committed_mw,
        capacity_eur=capacity_eur,
        penalty_eur=penalty_eur,
        net_eur=capacity_eur - penalty_eur,
        n_events=jnp.sum(events.valid, axis=-1),
        n_compliant=jnp.sum(events.valid & events.compliant, axis=-1),
    )


# ---------------------------------------------------------------------------
# Per-event Python reference: independent control flow, shared physics
# ---------------------------------------------------------------------------


def reserve_replay_reference(freq, mu_h, t_amb_h, valid_s, product_idx, rho,
                             design_mw, pue_design, *,
                             pue_aware: bool = True,
                             e_max: int = E_MAX) -> dict:
    """The pre-batching shape of this computation: numpy crossing
    detection plus a Python loop over events.  Same detection semantics
    and the same jitted per-event physics as :func:`reserve_replay`, so
    verdicts match the scan exactly; used as the parity oracle and the
    speed baseline of ``benchmarks/e9_reserve.py``.
    """
    p = _PRODUCTS[int(product_idx)]
    trig_hz = np.float32(p.trigger_hz)
    min_dur_i = int(p.min_duration_s)
    min_dur_f = np.float32(p.min_duration_s)
    f = np.asarray(freq, np.float32)
    mu_h = np.asarray(mu_h, np.float32)
    t_amb_h = np.asarray(t_amb_h, np.float32)
    T, H = f.shape[0], mu_h.shape[0]
    valid_s = int(valid_s)
    design_mw_f = np.float32(design_mw)

    below = f < trig_hz
    cand = np.flatnonzero(below[:valid_s])

    # the same hoisted per-hour physics table the scan gathers from
    vh = {k: np.asarray(x) for k, x in _event_verdict_jit(
        mu_h, t_amb_h, np.float32(rho), int(product_idx),
        np.float32(pue_design), pue_aware=pue_aware).items()}

    def verdict(hour: int) -> dict:
        return {k: x[hour] for k, x in vh.items()}

    ev = dict(
        t_event_s=np.full(e_max, -1, np.int32),
        t_full_ms=np.zeros(e_max, np.float32),
        sustain_s=np.zeros(e_max, np.float32),
        delivered_mw=np.zeros(e_max, np.float32),
        delivered_frac=np.zeros(e_max, np.float32),
        budget_ok=np.zeros(e_max, bool),
        sustain_ok=np.zeros(e_max, bool),
        delivered_ok=np.zeros(e_max, bool),
        compliant=np.zeros(e_max, bool),
        valid=np.zeros(e_max, bool),
    )
    n, active_s = 0, 0
    shed_it_mwh = np.float32(0.0)
    ptr = 0
    while ptr < cand.size:
        t = int(cand[ptr])
        v = verdict(min(t // 3600, H - 1))
        if n < e_max:
            sustain_s = np.float32(min(min_dur_f, np.float32(valid_s - t)))
            sustain_ok = bool(sustain_s >= min_dur_f)
            ev["t_event_s"][n] = t
            ev["t_full_ms"][n] = v["t_full_ms"]
            ev["sustain_s"][n] = sustain_s
            ev["delivered_mw"][n] = np.float32(
                v["delivered_unit"] * design_mw_f)
            ev["delivered_frac"][n] = v["delivered_frac"]
            ev["budget_ok"][n] = bool(v["budget_ok"])
            ev["sustain_ok"][n] = sustain_ok
            ev["delivered_ok"][n] = bool(v["delivered_ok"])
            ev["compliant"][n] = (bool(v["budget_ok"]) and sustain_ok
                                  and bool(v["delivered_ok"]))
            ev["valid"][n] = True
            n += 1
        # release: first second >= t + min_dur - 1 (hold expired) with
        # frequency back above the trigger; otherwise the event runs to
        # the end of the trace
        s0 = t + min_dur_i - 1
        if s0 >= T:
            last = T - 1
        else:
            rel = np.flatnonzero(~below[s0:])
            last = s0 + int(rel[0]) if rel.size else T - 1
        for s in range(t, min(last, T - 1) + 1):
            if s < valid_s:
                vs = verdict(min(s // 3600, H - 1))
                active_s += 1
                shed_it_mwh = np.float32(
                    shed_it_mwh
                    + np.float32(vs["rho_it"] * design_mw_f) / 3600.0)
        ptr = int(np.searchsorted(cand, last + 1, side="left"))
    return dict(events=ReserveEvents(**ev), n_events=n, active_s=active_s,
                shed_it_mwh=shed_it_mwh)


# ---------------------------------------------------------------------------
# Proportional products: droop activation and block settlement
# ---------------------------------------------------------------------------


def droop_activation(freq, deadband_hz, full_hz):
    """Signed activation in [-1, 1] of a proportional product at 1 Hz:
    ``sign(df) * max(|df| - deadband, 0) / (full - deadband)`` with
    ``df = 50 - f``.  Positive under-frequency (the site draws less),
    negative over-frequency (it draws more), 0 inside the deadband."""
    df = markets.NOMINAL_HZ - freq
    mag = jnp.maximum(jnp.abs(df) - deadband_hz, 0.0) / (full_hz
                                                          - deadband_hz)
    return jnp.clip(jnp.sign(df) * mag, -1.0, 1.0)


def to_blocks(per_hour, block_h: int):
    """(..., H) hourly sums -> (..., ceil(H / block_h)) block sums; the
    last block is right-padded with zero hours."""
    h = per_hour.shape[-1]
    pad = (-h) % block_h
    x = jnp.pad(per_hour, [(0, 0)] * (per_hour.ndim - 1) + [(0, pad)])
    return jnp.sum(x.reshape(x.shape[:-1] + (-1, block_h)), axis=-1)


def block_verdicts(abs_err, active_s, committed, tol):
    """A block complies when its mean |delivered - required| response over
    its active seconds is at most ``tol`` times the committed band (the
    same units as ``abs_err / active_s``); a block with no active second
    has nothing to answer and complies."""
    mean_err = abs_err / jnp.maximum(active_s, 1.0)
    return (active_s == 0) | (mean_err <= tol * committed)


def block_clawback(ok, valid, capacity_eur) -> jax.Array:
    """Revenue forfeited over blocks: each valid block that failed its
    verdict loses its whole capacity payment."""
    return jnp.sum(jnp.where(valid & ~ok, capacity_eur, 0.0), axis=-1)
