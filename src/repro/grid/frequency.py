"""Pure-jnp 1 Hz grid-frequency synthesis (the E9 event stream).

The numpy :class:`repro.grid.markets.FFRTriggerGen` draws Poisson
under-frequency events and paints them onto a random-walk baseline one
Python loop iteration at a time.  This module is the device-side
equivalent: every step is a jnp primitive, events live in fixed-size
padded arrays (:class:`EventBatch`), and every function broadcasts over a
leading scenario axis, so the reserve engine synthesises hundreds of
scenario-days of frequency as one compiled ``vmap`` call.

Trace semantics are pinned element-wise against
``FFRTriggerGen.frequency_trace`` (see tests/test_frequency.py): each
event ramps down from 50 Hz at ``rocof`` Hz/s, bottoms at ``nadir`` and
recovers linearly over ``recovery_s``; events are applied in ascending-time
order with overwrite semantics on overlapping seconds.

A proportional product (FCR-CE, ``markets.DROOP``) answers the whole
deviation, so its baseline is not the fading random walk but a stationary
mean-reverting (Ornstein-Uhlenbeck) deviation of the droop rule's
``ou_sigma_hz`` and ``ou_tau_s`` (:func:`ou_baseline`), with the same
Poisson excursions painted over it.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.grid.markets import DROOP, FR_PRODUCTS, NOMINAL_HZ, PRODUCT_ORDER

MAX_EVENTS = 64                 # Poisson(rate * days) tail headroom
DEFAULT_ROCOF_HZ_S = 0.2
DEFAULT_EVENTS_PER_DAY = 4.0
RECOVERY_RANGE_S = (60.0, 600.0)

# per-product event-sampling bounds, indexable by a traced product index
# (same nadir window as FFRTriggerGen.sample_day)
_NADIR_LO = tuple(FR_PRODUCTS[n].full_delivery_hz - 0.1 for n in PRODUCT_ORDER)
_NADIR_HI = tuple(FR_PRODUCTS[n].trigger_hz - 0.02 for n in PRODUCT_ORDER)


class EventBatch(NamedTuple):
    """Padded per-scenario event set; all fields (..., E)-shaped."""

    t0_s: jax.Array       # int32 event start second
    nadir_hz: jax.Array   # float32
    recovery_s: jax.Array  # float32
    valid: jax.Array      # bool, first-n entries (ascending t0) are real


def sample_events(key, n_seconds: int, product_idx,
                  events_per_day=DEFAULT_EVENTS_PER_DAY,
                  max_events: int = MAX_EVENTS) -> EventBatch:
    """Poisson under-frequency events over ``n_seconds`` of one scenario.

    ``product_idx`` may be traced (int32 into PRODUCT_ORDER): the nadir
    window follows the product's trigger/full-delivery band exactly as
    ``FFRTriggerGen.sample_day`` does.
    """
    kn, kt, ka, kr = jax.random.split(key, 4)
    lam = jnp.asarray(events_per_day, jnp.float32) * n_seconds / 86_400.0
    n = jnp.minimum(jax.random.poisson(kn, lam), max_events)
    slot = jnp.arange(max_events)
    t_raw = jax.random.uniform(kt, (max_events,), minval=0.0,
                               maxval=float(n_seconds))
    # sort the *valid* draws ascending without biasing them early: invalid
    # slots sort to +inf, the permutation is applied to every field
    order = jnp.argsort(jnp.where(slot < n, t_raw, jnp.inf))
    lo = jnp.asarray(_NADIR_LO, jnp.float32)[product_idx]
    hi = jnp.asarray(_NADIR_HI, jnp.float32)[product_idx]
    nadir = jax.random.uniform(ka, (max_events,), minval=lo, maxval=hi)
    rec = jax.random.uniform(kr, (max_events,), minval=RECOVERY_RANGE_S[0],
                             maxval=RECOVERY_RANGE_S[1])
    return EventBatch(
        t0_s=t_raw[order].astype(jnp.int32),
        nadir_hz=nadir[order],
        recovery_s=rec[order],
        valid=slot < n,
    )


def baseline_wander(key, n_seconds: int) -> jax.Array:
    """Nominal 50 Hz plus the normalised random-walk wander of
    ``FFRTriggerGen.frequency_trace``, the baseline of the triggered
    products.

    ``cumsum / sqrt(t)`` keeps the wander's standard deviation at about
    10 mHz, but the walk is normalised by the elapsed time, so late in a
    day it hardly moves from second to second.  It stays far from the
    fast-product triggers (FFR 49.7, FCR-D 49.9); a proportional product,
    which answers every millihertz, takes :func:`ou_baseline` instead.
    """
    g = jax.random.normal(key, (n_seconds,))
    scale = jnp.sqrt(jnp.arange(1, n_seconds + 1, dtype=jnp.float32))
    return NOMINAL_HZ + 0.01 * jnp.cumsum(g) / scale


def ou_baseline(key, n_seconds: int, sigma_hz, tau_s) -> jax.Array:
    """Nominal 50 Hz plus a stationary Ornstein-Uhlenbeck deviation at 1 Hz:
    ``x_0 = sigma g_0``, ``x_t = phi x_{t-1} + sigma sqrt(1 - phi^2) g_t``
    with ``phi = exp(-1 / tau_s)``, so every second has standard deviation
    ``sigma_hz`` and neighbouring seconds correlate as ``phi``.

    The recursion is linear, so it runs as a parallel prefix
    (``associative_scan`` over the affine maps ``x -> phi x + b_t``), not
    as ``n_seconds`` dependent steps.
    """
    phi = jnp.exp(-1.0 / jnp.asarray(tau_s, jnp.float32))
    g = jax.random.normal(key, (n_seconds,))
    first = jnp.arange(n_seconds) == 0
    b = sigma_hz * jnp.where(first, 1.0, jnp.sqrt(1.0 - phi * phi)) * g
    a = jnp.broadcast_to(phi, (n_seconds,))

    def compose(earlier, later):
        return later[0] * earlier[0], later[0] * earlier[1] + later[1]

    _, x = jax.lax.associative_scan(compose, (a, b))
    return NOMINAL_HZ + x


def apply_events(f_base, events: EventBatch,
                 rocof_hz_s: float = DEFAULT_ROCOF_HZ_S) -> jax.Array:
    """Paint the event ramps onto a baseline trace (overwrite semantics).

    A ``lax.scan`` over the (small, padded) event axis replays the numpy
    generator's event loop exactly: later events win on overlap.  O(E*T)
    elementwise, vmappable over a leading scenario axis on both arguments.
    """
    f_base = jnp.asarray(f_base, jnp.float32)
    idx = jnp.arange(f_base.shape[-1], dtype=jnp.int32)

    def paint(f, ev):
        t0, nadir, rec, valid = ev
        fall_s = jnp.maximum(
            jnp.floor((NOMINAL_HZ - nadir) / rocof_hz_s), 1.0
        ).astype(jnp.int32)
        k = idx - t0
        v_fall = NOMINAL_HZ - rocof_hz_s * k
        kr = k - fall_s
        v_rec = nadir + (NOMINAL_HZ - nadir) * kr / rec
        f = jnp.where(valid & (k >= 0) & (k < fall_s), v_fall, f)
        in_rec = (kr >= 0) & (kr < jnp.floor(rec).astype(jnp.int32))
        return jnp.where(valid & in_rec, v_rec, f), None

    f, _ = jax.lax.scan(paint, f_base, events)
    return f


def frequency_trace(key, n_seconds: int, product_idx=0,
                    events_per_day=DEFAULT_EVENTS_PER_DAY,
                    rocof_hz_s: float = DEFAULT_ROCOF_HZ_S,
                    max_events: int = MAX_EVENTS,
                    proportional: bool = False):
    """One scenario's (trace, events).  Pure jnp; vmapped by the batch API.
    ``proportional`` (static) takes the product's OU baseline in place of
    the wander."""
    kw, ke = jax.random.split(key)
    events = sample_events(ke, n_seconds, product_idx, events_per_day,
                           max_events)
    if proportional:
        base = ou_baseline(kw, n_seconds, DROOP.ou_sigma_hz,
                           DROOP.ou_tau_s)
    else:
        base = baseline_wander(kw, n_seconds)
    return apply_events(base, events, rocof_hz_s), events


@partial(jax.jit, static_argnames=("n_seconds", "max_events",
                                   "proportional"))
def synthesize_frequency_batch(seeds, product_idx, *, n_seconds: int,
                               events_per_day=DEFAULT_EVENTS_PER_DAY,
                               max_events: int = MAX_EVENTS,
                               proportional: bool = False):
    """(N,) seeds + (N,) product indices -> ((N, T) traces, EventBatch).

    ONE compiled vmap: the whole scenario batch's frequency synthesis --
    Poisson draws, ramp painting, baseline -- in a single call.  A batch
    is all triggered or all ``proportional`` products (static).
    """
    seeds = jnp.asarray(seeds, jnp.uint32)
    product_idx = jnp.broadcast_to(jnp.asarray(product_idx, jnp.int32),
                                   seeds.shape)
    rate = jnp.broadcast_to(jnp.asarray(events_per_day, jnp.float32),
                            seeds.shape)

    def one(seed, pidx, r):
        return frequency_trace(jax.random.PRNGKey(seed), n_seconds, pidx,
                               r, max_events=max_events,
                               proportional=proportional)

    return jax.vmap(one)(seeds, product_idx, rate)
