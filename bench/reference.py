"""Plain reference of what the benchmark's cells compute.

Written from the published model of GridPilot (paper Sect. 3-5, the
Nordic FFR / FCR-D product rules), with no import of the program under
test and nothing taken from it.  It follows the model's equations one
scenario (or one site) at a time and keeps every input on a flat seconds
axis:

  grid signals   hourly carbon intensity and ambient per (country, seed),
  Tier-3         hourly operating point: argmax over mu of
                 0.55 Q_FFR + 0.45 CFE with the sold band held fixed,
  hourly tier    schedule energy, carbon and token accounting,
  frequency      1 Hz trace with Poisson under-frequency events,
  seconds tier   reserve detection, duty shed, AR(4)/RLS prediction, cap
                 rebalance, plant power, meter (PUE), running sums,
  events         per-event verdicts at the pre-trigger power, settlement.

Every float is computed in ``dt``: float32 for the reference, bfloat16
for the control that must fail the comparison (``bench/control.py``).
Random draws use JAX's counter-based PRNG with the same key derivation
as the deployment's definition (scenario seed -> load and plant keys,
event seed x 100003 + seed -> frequency key), so the same seed names the
same scenario here and in the program.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# --- plant (paper E1 fit) ----------------------------------------------------
P_IDLE, ALPHA, BETA, GAMMA = 39.0, 0.027, 9.27e-5, 2.7
TDP, CAP_MIN, CAP_MAX = 300.0, 100.0, 300.0
F_MIN, F_MAX, F_VMIN, F_NOMINAL = 405.0, 1530.0, 945.0, 1480.0
GOV_SLEW, ACTUATE_DELAY_MS = 0.00344, 5.0
IDLE_FLOOR_W = 53.0
# demand archetypes: (mean, fast sigma, slow sigma); host mix 50/30/20 %
ARCHETYPES = ((0.97, 0.021, 0.012), (0.58, 0.008, 0.010),
              (0.95, 0.008, 0.02))
BURSTY_PERIOD_S, BURSTY_DUTY, BURSTY_LOW = 4.0, 0.5, 0.05
BURSTY_EDGE_JITTER_S, BURSTY_JITTER_FREQ_HZ = 0.12, 0.017
SLOW_FREQS_HZ = (0.031, 0.073, 0.127, 0.211)
# --- meter (paper Eq. 4) -----------------------------------------------------
T_FC_HI, T_FC_LO, T_REF = 25.0, 12.0, 18.0
SHARES = (0.55, 0.18, 0.15, 0.12)         # chiller, pumps, air, misc
PUMP_FLOOR, AIR_FLOOR = 0.20, 0.15
# --- products: FFR, FCR-D, FCR, aFRR, mFRR -----------------------------------
PRODUCTS = ("FFR", "FCR-D", "FCR", "aFRR", "mFRR")
BUDGET_MS = (700.0, 5000.0, 30000.0, 300000.0, 750000.0)
TRIGGER_HZ = (49.7, 49.9, 49.98, 49.99, 49.99)
FULL_HZ = (49.5, 49.5, 49.8, 49.9, 49.9)
MIN_DUR_S = (30.0, 60.0, 900.0, 3600.0, 3600.0)
PRICE_EUR_MW_H = (45.0, 18.0, 15.0, 9.0, 5.0)
# --- Tier-3 and settlement ---------------------------------------------------
MU_GRID = (0.4, 0.5, 0.6, 0.7, 0.8, 0.9)
RHO_MAX, W_FFR, W_CFE = 0.3, 0.55, 0.45
MIN_RESIDUAL, DELIVERY_TOL, PENALTY_H = 0.17, 0.02, 24.0
# --- workload mixes: train, inference, balanced ------------------------------
MIXES = ("train", "inference", "balanced")
CLOCK_W = (0.88, 0.15, 0.50)
TOKENS_PER_MW_S = (250e3, 400e3, 300e3)
# --- Tier-2 ------------------------------------------------------------------
RLS_ORDER, RLS_FORGET, RLS_P0 = 4, 0.97, 100.0
CKPT_COST_S = 30.0          # checkpoint and restore dead time per event
_COUNTRY = {
    "SE": (25.0, 0.02, 0.25, -4.0, 17.0, 0.25),
    "CH": (38.0, 0.06, 0.02, 0.0, 19.0, 0.35),
    "FR": (56.0, 0.05, 0.09, 5.0, 21.0, 0.6),
    "IT": (280.0, 0.12, 0.08, 8.0, 25.0, 1.0),
    "DE": (380.0, 0.12, 0.25, 2.0, 19.0, 1.3),
    "PL": (660.0, 0.08, 0.12, -1.0, 19.0, 0.45),
}


# ---------------------------------------------------------------------------
# Grid signals (host, float64 -> float32), one (country, seed, day) draw
# ---------------------------------------------------------------------------


def _wind(n, rng):
    phi = np.exp(-1.0 / 30.0)
    sig = np.sqrt(1 - phi * phi)
    x = np.zeros(n)
    v = rng.standard_normal(n)
    for t in range(1, n):
        x[t] = phi * x[t - 1] + sig * v[t]
    return np.tanh(0.8 * x)


def grid_signals(country: str, hours: int, seed: int, start_day: int):
    """(ci gCO2/kWh, t_amb degC), each (hours,) float32."""
    ci_mean, solar, wind_sh, t_w, t_s, vol = _COUNTRY[country]
    rs = seed * 101 + zlib.crc32(country.encode()) % 2**16
    rng = np.random.default_rng(rs)
    h = np.arange(hours, dtype=np.float64) + 24.0 * start_day
    hd = h % 24
    diurnal = 1.0 + (0.10 * np.cos(2 * np.pi * (hd - 19.0) / 24.0)
                     + 0.06 * np.cos(4 * np.pi * (hd - 8.0) / 24.0)
                     - 2.2 * solar * np.exp(-0.5 * ((hd - 13.0) / 2.6) ** 2))
    env = 1.0 + vol * (diurnal - 1.0)
    pull = 1.0 - vol * 0.4 * wind_sh / 0.25 * _wind(hours, rng)
    noise = 1.0 + 0.03 * vol * rng.standard_normal(hours)
    ci = np.clip(ci_mean * env * pull * noise, 0.05 * ci_mean, 3.0 * ci_mean)
    rng = np.random.default_rng(rs)
    hh = np.arange(hours, dtype=np.float64)
    doy = (float(start_day) + hh / 24.0) % 365.0
    season = 0.5 - 0.5 * np.cos(2 * np.pi * (doy - 15.0) / 365.0)
    t_amb = (t_w + (t_s - t_w) * season
             + 4.5 * np.sin(2 * np.pi * ((hh % 24) - 9.0) / 24.0)
             - 3.5 * _wind(hours, rng) + 1.2 * rng.standard_normal(hours))
    return ci.astype(np.float32), t_amb.astype(np.float32)


def scenario_table(specs, h_max: int) -> dict:
    """Host arrays of a list of scenario dicts (keys: country, seed,
    start_day, mw, pue_design, horizon_h, product, rho, event_seed, mix),
    hourly traces right-padded to ``h_max``."""
    n = len(specs)
    ci = np.zeros((n, h_max), np.float32)
    t_amb = np.full((n, h_max), T_REF, np.float32)
    mask = np.zeros((n, h_max), np.float32)
    cache = {}
    for i, s in enumerate(specs):
        k = (s["country"], s["seed"], s["start_day"], s["horizon_h"])
        if k not in cache:
            cache[k] = grid_signals(s["country"], s["horizon_h"], s["seed"],
                                    s["start_day"])
        h = s["horizon_h"]
        ci[i, :h], t_amb[i, :h] = cache[k]
        mask[i, :h] = 1.0

    def col(key, dtype):
        return np.asarray([s[key] for s in specs], dtype)

    return dict(
        ci=ci, t_amb=t_amb, mask=mask,
        seed=col("seed", np.int32), mw=col("mw", np.float32),
        pue_design=col("pue_design", np.float32),
        hours=col("horizon_h", np.int32),
        product=np.asarray([PRODUCTS.index(s["product"]) for s in specs],
                           np.int32),
        rho=col("rho", np.float32), event_seed=col("event_seed", np.int32),
        mix=np.asarray([MIXES.index(s["mix"]) for s in specs], np.int32))


# ---------------------------------------------------------------------------
# Physics, in the precision ``dt``
# ---------------------------------------------------------------------------


def _c(x, dt):
    return jnp.asarray(x, dt)


def power_model(f, load, dt):
    f2 = jnp.where(f >= F_VMIN, f * f, f * F_VMIN)
    return _c(P_IDLE + ALPHA * f, dt) + _c(BETA * f2, dt) * load \
        + _c(GAMMA, dt) * load


def freq_at_cap(cap, load):
    L = jnp.maximum(load, 1e-3)
    budget = cap - P_IDLE - GAMMA * L
    disc = ALPHA * ALPHA + 4.0 * BETA * L * jnp.maximum(budget, 0.0)
    f_quad = (-ALPHA + jnp.sqrt(disc)) / (2.0 * BETA * L)
    f_lin = budget / (ALPHA + BETA * F_VMIN * L)
    return jnp.clip(jnp.where(f_quad >= F_VMIN, f_quad, f_lin), F_MIN, F_MAX)


def _freq_at_cap_np(cap, load):
    budget = cap - P_IDLE - GAMMA * load
    f_quad = (-ALPHA + np.sqrt(ALPHA ** 2 + 4 * BETA * load * budget)) \
        / (2 * BETA * load)
    f = f_quad if f_quad >= F_VMIN else budget / (ALPHA + BETA * F_VMIN * load)
    return float(np.clip(f, F_MIN, F_MAX))


P_FLOOR_FRAC = (P_IDLE + ALPHA * F_MIN + BETA * F_MIN * F_VMIN + GAMMA) / TDP
P_IDLE_FRAC = P_IDLE / TDP
F_AT_TDP = _freq_at_cap_np(TDP, 1.0)
MEM_AT_TDP = 0.45 + 0.55 * F_AT_TDP / F_NOMINAL


def throughput_frac(clock_w, p):
    f = freq_at_cap(jnp.clip(p, P_FLOOR_FRAC, 1.0) * TDP, jnp.ones_like(p))
    r = clock_w * (f / F_AT_TDP) + (1.0 - clock_w) * (
        (0.45 + 0.55 * f / F_NOMINAL) / MEM_AT_TDP)
    duty = jnp.clip((p - P_IDLE_FRAC) / (P_FLOOR_FRAC - P_IDLE_FRAC), 0.0, 1.0)
    return jnp.where(p < P_FLOOR_FRAC, duty * r, r)


def pue(load, t_amb, pue_design):
    L = jnp.clip(load, 1e-3, 1.0)
    oh = pue_design - 1.0
    f_fc = jnp.clip((T_FC_HI - t_amb) / (T_FC_HI - T_FC_LO), 0.0, 1.0)
    f_ref = (T_FC_HI - T_REF) / (T_FC_HI - T_FC_LO)
    chiller = (oh * SHARES[0] / (1.0 - 0.85 * f_ref)) * L \
        * (1.0 + 0.45 * (1.0 - L)) * (1.0 - 0.85 * f_fc)
    pumps = oh * SHARES[1] * jnp.maximum(L * L, PUMP_FLOOR)
    air = oh * SHARES[2] * jnp.maximum(L * L * L, AIR_FLOOR)
    return 1.0 + (chiller + pumps + air + oh * SHARES[3]) / L


def meter_gain(mu, rho, t_amb, pue_design):
    """Meter-side delivery per unit of IT-side band shed from ``mu``."""
    rho = jnp.maximum(rho, 1e-6)
    lo = jnp.maximum(mu - rho, 0.02)
    return (mu * pue(mu, t_amb, pue_design)
            - lo * pue(lo, t_amb, pue_design)) / rho


def verdict(mu, t_amb, rho, product, pue_design):
    """One activation from operating point ``mu`` (PUE-aware band)."""
    mu = jnp.maximum(mu, 1e-3)
    rho_it = rho * pue_design / jnp.maximum(
        meter_gain(mu, rho, t_amb, pue_design), 1e-3)
    rho_it = jnp.clip(rho_it, 0.0, jnp.maximum(mu - MIN_RESIDUAL, 0.0))
    residual = jnp.maximum(mu - rho_it, 1e-3)
    t_full = ACTUATE_DELAY_MS + jnp.log(mu / residual) / GOV_SLEW
    delivered = meter_gain(mu, rho_it, t_amb, pue_design) * rho_it
    committed = rho * pue_design
    frac = jnp.where(committed > 0.0, delivered / committed, 1.0)
    return dict(rho_it=rho_it, t_full_ms=t_full,
                budget_ok=t_full <= jnp.asarray(BUDGET_MS)[product],
                delivered_unit=delivered, delivered_frac=frac,
                delivered_ok=frac >= 1.0 - DELIVERY_TOL)


def q_ffr(mu, rho, t_amb, pue_design):
    rho_it = rho * pue_design / jnp.maximum(
        meter_gain(mu, rho, t_amb, pue_design), 1e-3)
    rho_it = jnp.minimum(rho_it, mu - MIN_RESIDUAL)
    delivered = meter_gain(mu, rho_it, t_amb, pue_design) * rho_it
    acc = jnp.clip(delivered / jnp.maximum(rho * pue_design, 1e-6), 0.0, 1.0)
    q = jnp.power(rho / RHO_MAX, 0.25) * acc
    return jnp.where(mu - rho >= MIN_RESIDUAL, q, 0.0)


# ---------------------------------------------------------------------------
# Hourly tier: Tier-3 selection and schedule accounting (one scenario)
# ---------------------------------------------------------------------------


def hourly(ci, t_amb, mask, mw, pue_design, product, rho, mix, dt):
    valid = mask > 0
    lo = jnp.min(jnp.where(valid, ci, jnp.inf))
    hi = jnp.max(jnp.where(valid, ci, -jnp.inf))
    green = jnp.clip(1.0 - (ci - lo) / jnp.maximum(hi - lo, 1e-6), 0.0, 1.0)
    mus = _c(MU_GRID, dt)
    J = (_c(W_FFR, dt) * q_ffr(mus[None, :], rho, t_amb[:, None], pue_design)
         + _c(W_CFE, dt) * (green[:, None] * (mus / MU_GRID[-1])
                            + (1.0 - green[:, None])
                            * (1.0 - mus / MU_GRID[-1])))
    mu_h = jnp.where(valid, mus[jnp.argmax(J, axis=1)], 0.0)
    rho_h = jnp.where(valid, jnp.broadcast_to(rho, ci.shape), 0.0)
    # median CI of the valid hours (linear interpolation)
    xs = jnp.sort(jnp.where(valid, ci, jnp.inf))
    n = jnp.sum(valid)
    pos = 0.5 * (n.astype(dt) - 1.0)
    i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, ci.shape[0] - 1)
    i1 = jnp.clip(i0 + 1, 0, n - 1)
    w = pos - i0.astype(dt)
    green_ci = xs[i0] * (1.0 - w) + xs[i1] * w
    load = jnp.clip(mu_h, 0.05, 1.0)
    it_w = load * mw * mask
    fac_w = load * pue(load, t_amb, pue_design) * mw * mask
    is_green = ci <= green_ci
    clock_w = _c(CLOCK_W, dt)[mix]
    thr = jnp.sum(throughput_frac(clock_w, load) * mask)
    hv = jnp.maximum(jnp.sum(mask), 1.0)
    return dict(
        mu_h=mu_h, rho_h=rho_h,
        mean_mu=jnp.sum(mu_h * mask) / hv, mean_rho=jnp.sum(rho_h * mask) / hv,
        sched_it_mwh=jnp.sum(it_w), sched_fac_mwh=jnp.sum(fac_w),
        sched_co2_t=jnp.sum(fac_w * ci) / 1000.0,
        sched_co2_it_t=jnp.sum(it_w * ci) / 1000.0,
        sched_cfe_fac_mwh=jnp.sum(jnp.where(is_green, fac_w, 0.0)),
        cfe_mu=jnp.sum(jnp.where(is_green, mu_h, 0.0) * mask),
        sched_tokens_mtok=thr * 3600.0 * mw
        * _c(TOKENS_PER_MW_S, dt)[mix] / 1e6)


# ---------------------------------------------------------------------------
# Frequency and demand synthesis
# ---------------------------------------------------------------------------


def frequency(seed_u32, product, n_seconds, events_per_day, max_events, dt):
    """1 Hz grid frequency: a normalised random walk around 50 Hz with
    Poisson events (fall at 0.2 Hz/s to a nadir, linear recovery)."""
    kw, ke = jax.random.split(jax.random.PRNGKey(seed_u32))
    kn, kt, ka, kr = jax.random.split(ke, 4)
    lam = jnp.asarray(events_per_day, jnp.float32) * n_seconds / 86_400.0
    n = jnp.minimum(jax.random.poisson(kn, lam), max_events)
    slot = jnp.arange(max_events)
    t_raw = jax.random.uniform(kt, (max_events,), minval=0.0,
                               maxval=float(n_seconds))
    order = jnp.argsort(jnp.where(slot < n, t_raw, jnp.inf))
    lo = jnp.asarray([h - 0.1 for h in FULL_HZ], jnp.float32)[product]
    hi = jnp.asarray([h - 0.02 for h in TRIGGER_HZ], jnp.float32)[product]
    nadir = jax.random.uniform(ka, (max_events,), minval=lo, maxval=hi)
    rec = jax.random.uniform(kr, (max_events,), minval=60.0, maxval=600.0)
    t0s, nadirs, recs = (t_raw[order].astype(jnp.int32),
                         nadir[order].astype(dt), rec[order].astype(dt))
    g = jax.random.normal(kw, (n_seconds,)).astype(dt)
    f = 50.0 + 0.01 * jnp.cumsum(g) / jnp.sqrt(
        jnp.arange(1, n_seconds + 1, dtype=dt))
    idx = jnp.arange(n_seconds, dtype=jnp.int32)

    def paint(f, e):
        t0, nad, r, ok = e
        fall = jnp.maximum(jnp.floor((50.0 - nad) / 0.2),
                           1.0).astype(jnp.int32)
        k = idx - t0
        f = jnp.where(ok & (k >= 0) & (k < fall), 50.0 - 0.2 * k.astype(dt), f)
        kr_ = k - fall
        rising = (kr_ >= 0) & (kr_ < jnp.floor(r).astype(jnp.int32))
        return jnp.where(ok & rising,
                         nad + (50.0 - nad) * kr_.astype(dt) / r, f), None

    f, _ = jax.lax.scan(paint, f, (t0s, nadirs, recs, slot < n))
    return f


def load_params(n_hosts: int, key, dt):
    kinds = ([0] * (n_hosts // 2) + [1] * (3 * n_hosts // 10)
             + [2] * (n_hosts - n_hosts // 2 - 3 * n_hosts // 10))
    stats = np.asarray([ARCHETYPES[k] for k in kinds], np.float32).T
    k_fast, k_ph, k_jit = jax.random.split(key, 3)
    return dict(
        mean=_c(stats[0], dt), fast_sigma=_c(stats[1], dt),
        slow_sigma=_c(stats[2], dt),
        phases=jax.random.uniform(k_ph, (n_hosts, 4), minval=0.0,
                                  maxval=2 * jnp.pi).astype(dt),
        bursty=jnp.asarray(np.asarray(kinds) == 2),
        duty_phase=_c(np.asarray(kinds) * 0.37, dt),
        jitter_ph=jax.random.uniform(k_jit, (n_hosts,),
                                     maxval=6.28).astype(dt),
        fast_key=k_fast)


def demand_rows(p, tf, fast, dt):
    """(K,) seconds and (K, H) white noise -> (K, H) demand in [0, 1]."""
    ang = 2 * jnp.pi * _c(SLOW_FREQS_HZ, dt) * tf[:, None]          # (K, 4)
    slow = jnp.sum(jnp.sin(ang)[:, None, :] * jnp.cos(p["phases"])[None]
                   + jnp.cos(ang)[:, None, :] * jnp.sin(p["phases"])[None],
                   axis=-1) / 2.0                                      # (K, H)
    base = p["mean"] + p["slow_sigma"] * slow + p["fast_sigma"] * fast
    aj = 2 * jnp.pi * BURSTY_JITTER_FREQ_HZ * tf
    jit_t = BURSTY_EDGE_JITTER_S * (
        jnp.sin(aj)[:, None] * jnp.cos(p["jitter_ph"])[None]
        + jnp.cos(aj)[:, None] * jnp.sin(p["jitter_ph"])[None])
    frac = jnp.mod((tf[:, None] + jit_t) / BURSTY_PERIOD_S + p["duty_phase"],
                   1.0)
    bursty = jnp.where(frac < BURSTY_DUTY, base, BURSTY_LOW + 0.01 * fast)
    return jnp.clip(jnp.where(p["bursty"], bursty, base), 0.0, 1.0)


def hour_demand(p, hour, dt):
    tf = (hour * 3600).astype(dt) + jnp.arange(3600, dtype=dt)
    fast = jax.random.normal(jax.random.fold_in(p["fast_key"], hour),
                             (3600,) + p["mean"].shape).astype(dt)
    return demand_rows(p, tf, fast, dt)


# ---------------------------------------------------------------------------
# Seconds tier: one site, one second
# ---------------------------------------------------------------------------

ACC_KEYS = ("n_s", "n_warm", "err", "track", "load", "fac", "chip_mean",
            "chip_p95", "shed_s", "shed_it", "thr")


def site_init(n_hosts: int, chips: int, key, dt) -> dict:
    return dict(
        theta=jnp.zeros((n_hosts, RLS_ORDER), dt).at[:, 0].set(1.0),
        P=jnp.broadcast_to(jnp.eye(RLS_ORDER, dtype=dt) * RLS_P0,
                           (n_hosts, RLS_ORDER, RLS_ORDER)),
        hist=jnp.zeros((n_hosts, RLS_ORDER), dt),
        steps=jnp.zeros((n_hosts,), jnp.int32),
        chip_power=jnp.full((n_hosts, chips), P_IDLE, dt),
        caps=jnp.full((n_hosts, chips), CAP_MAX, dt),
        key=key, last_load=_c(P_IDLE / TDP, dt),
        in_event=jnp.asarray(False), hold=jnp.asarray(0, jnp.int32),
        acc={k: _c(0.0, dt) for k in ACC_KEYS})


def second(s: dict, demand, below, in_hor, t, hp: dict, *, chips: int,
           chip_tdp: float, warmup_s: int, dt):
    """Advance one site by one second.  ``hp``: this hour's mu, rho,
    t_amb, rho_it, plus the site's min_dur (int), pue_design, clock_w.
    Returns (state, (triggered, shedding, load at the start))."""
    # reserve detection: trigger on a fresh crossing, hold min_dur seconds,
    # release once held out and the frequency is back above the trigger
    trig = ~s["in_event"] & below & in_hor
    in_ev = s["in_event"] | trig
    hold = jnp.where(trig, hp["min_dur"], s["hold"])
    hold = jnp.where(in_ev, jnp.maximum(hold - 1, 0), hold)
    shed = in_ev & in_hor
    in_ev = in_ev & ~((hold == 0) & ~below)

    n_hosts = demand.shape[0]
    design_host = chips * chip_tdp
    design_it = n_hosts * design_host
    mu, rho = hp["mu"], hp["rho"]
    frac = jnp.where(shed, mu - rho, mu)
    envelope = frac * design_it
    host_env = jnp.full((n_hosts,), 1.0, dt) * (frac * design_host)
    load = demand * mu / 0.9 * jnp.where(shed, frac / jnp.maximum(mu, 1e-3),
                                         1.0)
    key, k1 = jax.random.split(s["key"])
    # Tier-2: AR(4) prediction, proportional cap split inside the envelope
    pred = jnp.sum(s["theta"] * s["hist"], axis=1) * design_host
    prev = jnp.maximum(s["chip_power"], P_IDLE)
    scale = jnp.where(pred > host_env, host_env / jnp.maximum(pred, 1e-3),
                      1.0)
    share = prev * scale[:, None]
    room = jnp.maximum(host_env[:, None] - jnp.sum(share, 1, keepdims=True),
                       0.0)
    caps = jnp.clip(share + room / chips, CAP_MIN, CAP_MAX)
    # Tier-1 + plant, quasi-static over the second
    noise = jax.random.normal(k1, (n_hosts, chips)).astype(dt)
    target = jnp.minimum(power_model(F_NOMINAL, load[:, None], dt)
                         + 2.0 * noise, caps)
    shed_target = jnp.clip(frac * chip_tdp, IDLE_FLOOR_W, caps)
    power = jnp.where(shed, jnp.minimum(target, shed_target), target)
    host_power = jnp.sum(power, axis=1)
    # RLS with forgetting on normalised host power
    u = host_power / design_host
    phi, P = s["hist"], s["P"]
    err = u - jnp.sum(s["theta"] * phi, axis=1)
    Pphi = jnp.sum(P * phi[:, None, :], axis=2)
    gain = Pphi / (RLS_FORGET + jnp.sum(phi * Pphi, axis=1))[:, None]
    theta = s["theta"] + gain * err[:, None]
    Pn = (P - gain[:, :, None] * Pphi[:, None, :]) / RLS_FORGET
    Pn = 0.5 * (Pn + jnp.swapaxes(Pn, 1, 2))
    tr = jnp.trace(Pn, axis1=1, axis2=2)
    Pn = Pn * jnp.minimum(1e4 * RLS_ORDER / jnp.maximum(tr, 1e-9),
                          1.0)[:, None, None]
    warm = s["steps"] >= RLS_ORDER
    theta = jnp.where(warm[:, None], theta, s["theta"])
    Pn = jnp.where(warm[:, None, None], Pn, P)
    hist = jnp.concatenate([u[:, None], phi[:, :-1]], axis=1)
    # meter
    it = jnp.sum(host_power)
    L = it / design_it
    fac = it * pue(L, hp["t_amb"], hp["pue_design"])
    g = in_hor.astype(dt)
    w = g * (t >= warmup_s).astype(dt)
    a = s["acc"]
    acc = dict(
        n_s=a["n_s"] + g, n_warm=a["n_warm"] + w,
        err=a["err"] + w * jnp.mean(jnp.abs(err) * design_host) / design_host,
        track=a["track"] + w * jnp.abs(it - envelope)
        / jnp.maximum(envelope, 1.0),
        load=a["load"] + g * L, fac=a["fac"] + g * fac / design_it,
        chip_mean=a["chip_mean"] + g * jnp.mean(power),
        chip_p95=a["chip_p95"] + g * jnp.percentile(power, 95.0),
        shed_s=a["shed_s"] + shed.astype(dt),
        shed_it=a["shed_it"] + hp["rho_it"] * shed.astype(dt),
        thr=a["thr"] + g * throughput_frac(hp["clock_w"], L))
    new = dict(theta=theta, P=Pn, hist=hist, steps=s["steps"] + 1,
               chip_power=power, caps=caps, key=key, last_load=L,
               in_event=in_ev, hold=hold, acc=acc)
    return new, (trig, shed, s["last_load"])


def site_tables(h, t_amb, product, pue_design, mix, dt) -> dict:
    """The per-site hourly tables the seconds tier reads."""
    v = verdict(h["mu_h"], t_amb, h["rho_h"], product, pue_design)
    return dict(mu=h["mu_h"], rho=h["rho_h"], t_amb=t_amb, rho_it=v["rho_it"],
                min_dur=jnp.asarray(MIN_DUR_S)[product].astype(jnp.int32),
                pue_design=pue_design, clock_w=_c(CLOCK_W, dt)[mix])


def _hour(tab: dict, hour):
    hour = jnp.minimum(hour, tab["mu"].shape[0] - 1)
    return dict(tab, mu=tab["mu"][hour], rho=tab["rho"][hour],
                t_amb=tab["t_amb"][hour], rho_it=tab["rho_it"][hour])


# ---------------------------------------------------------------------------
# A whole scenario: hourly tier, seconds tier, events, settlement
# ---------------------------------------------------------------------------


def _in_dt(sc: dict, dt) -> dict:
    return {k: (v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating) else v)
            for k, v in sc.items()}


def scenario(sc: dict, *, n_hosts: int, chips: int, chip_tdp: float,
             e_max: int, events_per_day: float, max_freq_events: int,
             warmup_s: int, with_seconds: bool, dt) -> dict:
    f = _in_dt(sc, dt)
    h = hourly(f["ci"], f["t_amb"], f["mask"], f["mw"], f["pue_design"],
               f["product"], f["rho"], f["mix"], dt)
    if not with_seconds:
        return h
    h_max = f["ci"].shape[0]
    T = h_max * 3600
    valid_s = f["hours"] * 3600
    freq = frequency(
        f["event_seed"].astype(jnp.uint32) * jnp.uint32(100_003)
        + f["seed"].astype(jnp.uint32), f["product"], T, events_per_day,
        max_freq_events, dt)
    below = freq < _c(TRIGGER_HZ, dt)[f["product"]]
    load_key, scan_key = jax.random.split(jax.random.PRNGKey(f["seed"]), 2)
    lp = load_params(n_hosts, load_key, dt)
    demand = jax.vmap(lambda b: hour_demand(lp, b, dt))(
        jnp.arange(h_max)).reshape(T, n_hosts)
    tab = site_tables(h, f["t_amb"], f["product"], f["pue_design"], f["mix"],
                      dt)
    t_all = jnp.arange(T, dtype=jnp.int32)

    def step(s, x):
        d, b, t = x
        return second(s, d, b, t < valid_s, t, _hour(tab, t // 3600),
                      chips=chips, chip_tdp=chip_tdp, warmup_s=warmup_s,
                      dt=dt)

    s, (trig, shed, load_sec) = jax.lax.scan(
        step, site_init(n_hosts, chips, scan_key, dt), (demand, below, t_all))
    t_ev = jnp.nonzero(trig, size=e_max, fill_value=T)[0].astype(jnp.int32)
    valid = t_ev < T
    hour_ev = jnp.minimum(t_ev // 3600, h_max - 1)
    min_dur = jnp.asarray(MIN_DUR_S, dt)[f["product"]]
    sustain_ok = jnp.minimum(min_dur, (valid_s - t_ev).astype(dt)) >= min_dur
    v_s = verdict(h["mu_h"], f["t_amb"], h["rho_h"], f["product"],
                  f["pue_design"])
    sched_ok = (v_s["budget_ok"][hour_ev] & v_s["delivered_ok"][hour_ev]
                & sustain_ok & valid)
    v = verdict(load_sec[jnp.clip(t_ev, 0, T - 1)], f["t_amb"][hour_ev],
                h["rho_h"][hour_ev], f["product"], f["pue_design"])
    frac = jnp.where(valid, v["delivered_frac"], 0.0)
    t_full = jnp.where(valid, v["t_full_ms"], 0.0)
    budget_ok = valid & v["budget_ok"]
    delivered_ok = valid & v["delivered_ok"]
    compliant = budget_ok & sustain_ok & delivered_ok
    price = _c(PRICE_EUR_MW_H, dt)[f["product"]]
    committed = h["rho_h"] * f["mw"] * f["pue_design"]
    capacity = price * jnp.sum(committed * f["mask"])
    miss = (~(budget_ok & sustain_ok)).astype(dt)
    penalty = jnp.sum(jnp.where(
        valid, price * committed[hour_ev] * PENALTY_H
        * (jnp.clip(1.0 - frac, 0.0, 1.0) + miss), 0.0))
    a = s["acc"]
    n = jnp.maximum(a["n_s"], 1.0)
    nw = jnp.maximum(a["n_warm"], 1.0)
    clock_w = _c(CLOCK_W, dt)[f["mix"]]
    tok_unit = f["mw"] * _c(TOKENS_PER_MW_S, dt)[f["mix"]] / 1e6
    n_ev = jnp.sum(valid).astype(dt)
    thr_ref = throughput_frac(clock_w, _c(MU_GRID[-1], dt))
    tokens = a["thr"] * tok_unit
    ckpt = n_ev * CKPT_COST_S * thr_ref * tok_unit
    return dict(
        h,
        ar4_mae_norm=a["err"] / nw, tracking_err_mean=a["track"] / nw,
        chip_power_mean=a["chip_mean"] / n, chip_power_p95=a["chip_p95"] / n,
        it_mwh=a["load"] * f["mw"] / 3600.0,
        fac_mwh=a["fac"] * f["mw"] / 3600.0,
        n_events=jnp.sum(valid).astype(jnp.int32),
        active_s=a["shed_s"].astype(jnp.int32),
        shed_it_mwh=a["shed_it"] * f["mw"] / 3600.0,
        committed_mw=jnp.sum(committed * f["mask"])
        / jnp.maximum(jnp.sum(f["mask"]), 1.0),
        capacity_eur=capacity, penalty_eur=penalty, net_eur=capacity - penalty,
        n_compliant=jnp.sum(compliant).astype(jnp.int32),
        n_compliant_sched=jnp.sum(sched_ok).astype(jnp.int32),
        thr_mean=a["thr"] / n, tokens_mtok=tokens, tokens_ckpt_mtok=ckpt,
        tokens_lost_mtok=a["n_s"] * thr_ref * tok_unit - tokens + ckpt,
        t_event_s=jnp.where(valid, t_ev, -1), ev_valid=valid,
        ev_delivered_frac=frac, ev_t_full_ms=t_full,
        ev_budget_ok=budget_ok, ev_sustain_ok=valid & sustain_ok,
        ev_delivered_ok=delivered_ok)


_SCEN_STATIC = ("n_hosts", "chips", "chip_tdp", "e_max", "events_per_day",
                "max_freq_events", "warmup_s", "with_seconds", "dt")


@partial(jax.jit, static_argnames=_SCEN_STATIC)
def _scenarios_jit(tab, **kw):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(partial(scenario, **kw))(tab)


def _split_over(devices, kw: dict):
    """``_scenarios_jit`` with its scenario axis split over ``devices``,
    each chip computing its own scenarios with no exchange, and the
    sharding its input takes."""
    spec = jax.sharding.PartitionSpec("s")
    mesh = jax.sharding.Mesh(np.asarray(devices), ("s",))
    return jax.sharding.NamedSharding(mesh, spec), jax.jit(jax.shard_map(
        lambda tab: _scenarios_jit(tab, **kw), mesh=mesh, in_specs=spec,
        out_specs=spec, check_vma=False))


def run_scenarios(specs, engine: dict, *, dt=jnp.float32, block: int = 512,
                  h_max: int | None = None, devices=None) -> dict:
    """Every scenario's outputs as host numpy, computed ``block`` at a
    time on each of ``devices`` (default: the first device).  ``engine``:
    n_hosts, chips_per_host, chip_tdp, e_max, events_per_day,
    max_freq_events, warmup_s, with_seconds."""
    h_max = h_max or max(s["horizon_h"] for s in specs)
    kw = dict(n_hosts=engine["n_hosts"], chips=engine["chips_per_host"],
              chip_tdp=engine["chip_tdp"], e_max=engine["e_max"],
              events_per_day=engine["events_per_day"],
              max_freq_events=engine["max_freq_events"],
              warmup_s=engine["warmup_s"],
              with_seconds=engine["with_seconds"], dt=dt)
    devices = list(devices or jax.devices()[:1])
    n_dev, run = len(devices), partial(_scenarios_jit, **kw)
    size = block
    if n_dev > 1:
        # as many blocks as one chip would take, split evenly over the chips
        sharding, run = _split_over(devices, kw)
        n_blocks = -(-len(specs) // (block * n_dev))
        size = -(-len(specs) // (n_blocks * n_dev)) * n_dev
    parts = []
    for lo in range(0, len(specs), size):
        part = specs[lo:lo + size]
        tab = scenario_table(part, h_max)
        pad = size - len(part) if len(specs) > size or n_dev > 1 else 0
        if pad:
            tab = {k: np.concatenate([v, np.repeat(v[-1:], pad, 0)])
                   for k, v in tab.items()}
        if n_dev > 1:
            tab = jax.device_put(tab, sharding)
        out = jax.tree.map(np.asarray, run(tab))
        parts.append({k: v[:len(part)] for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


# ---------------------------------------------------------------------------
# The online service: resident sites advanced one tick at a time
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n_hosts", "chips", "chip_tdp",
                                   "warmup_s", "sched_s", "dt"))
def _service_jit(tab, below, n_ticks, *, n_hosts, chips, chip_tdp, warmup_s,
                 sched_s, dt):
    def one(sc, below_site):
        f = _in_dt(sc, dt)
        h = hourly(f["ci"], f["t_amb"], f["mask"], f["mw"], f["pue_design"],
                   f["product"], f["rho"], f["mix"], dt)
        site = site_tables(h, f["t_amb"], f["product"], f["pue_design"],
                           f["mix"], dt)
        load_key, scan_key = jax.random.split(jax.random.PRNGKey(f["seed"]),
                                              2)
        lp = load_params(n_hosts, load_key, dt)

        def tick(carry, x):
            s, shed_last = carry
            t, b = x
            t_s = jnp.mod(t, sched_s)
            fast = jax.random.normal(jax.random.fold_in(lp["fast_key"], t),
                                     (1, n_hosts)).astype(dt)
            d = demand_rows(lp, t_s.astype(dt)[None], fast, dt)[0]
            new, (_, shed, _) = second(
                s, d, b, jnp.asarray(True), t_s, _hour(site, t_s // 3600),
                chips=chips, chip_tdp=chip_tdp, warmup_s=warmup_s, dt=dt)
            go = t < n_ticks
            s = jax.tree.map(lambda a, b_: jnp.where(go, a, b_), new, s)
            return (s, jnp.where(go, shed, shed_last)), None

        (s, shed_last), _ = jax.lax.scan(
            tick,
            (site_init(n_hosts, chips, scan_key, dt), jnp.asarray(False)),
            (jnp.arange(below_site.shape[0], dtype=jnp.int32), below_site))
        return dict(s, shed_last=shed_last, mu0=h["mu_h"][0],
                    rho0=h["rho_h"][0])

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one, in_axes=(0, 1))(tab, below)


def run_service(sites, below, engine: dict, horizon_h: int, *,
                dt=jnp.float32, block: int = 256, bucket: int = 2048) -> dict:
    """Per-site state after ``len(below)`` ticks of the service, given each
    tick's per-site frequency-below-trigger flags, as host numpy.  The
    tick axis is padded to a multiple of ``bucket`` so runs of similar
    length share one compiled program."""
    n = below.shape[0]
    padded = np.zeros((-(-n // bucket) * bucket, below.shape[1]), bool)
    padded[:n] = below
    kw = dict(n_hosts=engine["n_hosts"], chips=engine["chips_per_host"],
              chip_tdp=engine["chip_tdp"], warmup_s=engine["warmup_s"],
              sched_s=horizon_h * 3600, dt=dt)
    parts = []
    for lo in range(0, len(sites), block):
        part = sites[lo:lo + block]
        tab = scenario_table(part, horizon_h)
        out = _service_jit(tab, jnp.asarray(padded[:, lo:lo + len(part)]),
                           jnp.int32(n), **kw)
        parts.append(jax.tree.map(np.asarray, out))
    return jax.tree.map(lambda *xs: np.concatenate(xs), *parts)
