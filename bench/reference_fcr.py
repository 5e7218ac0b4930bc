"""Plain reference of the Continental Europe FCR cell (``fcr-ce-day``).

Written from the product's public definition (Commission Regulation (EU)
2017/1485, Annex V: a 10 mHz insensitivity band, full activation at
+-200 mHz, the same band up and down; the FCR Cooperation's 4-hour
blocks) and the deployment's assumptions in
``bench/configs/continental-fcr.json``, with no import of the program
under test.  It takes the plain pieces of ``bench/reference.py`` (grid
signals, plant, meter, demand synthesis) and states the rest one
scenario at a time on a flat seconds axis:

  Tier-3         argmax over mu of 0.55 Q + 0.45 CFE with the sold band
                 held fixed, over the cells with headroom both ways
                 (mu - rho >= 0.17 and mu + rho <= 1),
  bands          the IT-side band of each direction that moves the meter
                 by rho x PUE_design at full activation,
  frequency      a stationary Ornstein-Uhlenbeck deviation around 50 Hz,
                 stepped second by second, with the Poisson excursions
                 painted over it,
  activation     a = sign(df) max(|df| - 0.010, 0) / 0.190 in [-1, 1],
  site           each simulated host stands for the site's hosts of its
                 archetype: demand is the archetype mean plus
                 sqrt(simulated chips / site chips) times the simulated
                 deviation, and the chips' plant noise shrinks alike,
  baseline       declared per hour before the day: each host's mean
                 demand at mu through the power model, held to its share
                 of the envelope, then the meter,
  seconds tier   the envelope mu - band x a, demand scaled by frac / mu,
                 AR(4)/RLS prediction, cap split, plant, meter,
  blocks         required rho x MW x PUE_design x a and delivered
                 (declared - metered) response summed per 4-hour block,
                 a block failing when its mean |delivered - required|
                 over its active seconds passes 0.10 of the committed MW,
                 settlement with a failed block's capacity forfeited.

Every float is computed in ``dt``: float32 for the reference, bfloat16
for the control that must fail the comparison (``bench/control.py``).
Random draws use the same key derivation as ``bench/reference.py``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

# --- the product: SOGL Annex V, FCR Cooperation ------------------------------
DEADBAND_HZ, FULL_HZ, BLOCK_H = 0.010, 0.200, 4
# the excursion window, as for the triggered products: nadir between
# (50 - full) - 0.1 and (50 - deadband) - 0.02
NADIR_LO, NADIR_HI = 49.8 - 0.1, 49.99 - 0.02
# --- the deployment's assumptions (continental-fcr.json) ---------------------
OU_SIGMA_HZ, OU_TAU_S = 0.020, 60.0
TRACKING_TOL, PRICE_EUR_MW_H = 0.10, 12.0


def _c(x, dt):
    return jnp.asarray(x, dt)


def host_means(n_hosts: int, dt):
    """(H,) long-run mean demand of each host's archetype; a bursty host
    spends BURSTY_DUTY of its time busy and the rest at BURSTY_LOW."""
    kinds = ([0] * (n_hosts // 2) + [1] * (3 * n_hosts // 10)
             + [2] * (n_hosts - n_hosts // 2 - 3 * n_hosts // 10))
    m = [ref.ARCHETYPES[k][0] if k != 2 else
         ref.BURSTY_DUTY * ref.ARCHETYPES[2][0]
         + (1.0 - ref.BURSTY_DUTY) * ref.BURSTY_LOW for k in kinds]
    return _c(np.asarray(m, np.float32), dt)


def declared(mu, t_amb, pue_design, means, chip_tdp):
    """The hour's declared facility power per unit of design IT."""
    load = jnp.clip(means[None, :] * mu[:, None] / 0.9, 0.0, 1.0)
    cap = jnp.clip(mu[:, None] * chip_tdp, ref.CAP_MIN, ref.CAP_MAX)
    chip = jnp.minimum(ref.power_model(ref.F_NOMINAL, load, load.dtype), cap)
    it = jnp.mean(chip, axis=1) / chip_tdp
    return it * ref.pue(it, t_amb, pue_design)


# ---------------------------------------------------------------------------
# Hourly tier with headroom both ways, and the two bands
# ---------------------------------------------------------------------------


def gain_up(mu, rho, t_amb, pue_design):
    """Meter-side delivery per unit of IT band raised from ``mu``."""
    rho = jnp.maximum(rho, 1e-6)
    hi = jnp.minimum(mu + rho, 1.0)
    return (hi * ref.pue(hi, t_amb, pue_design)
            - mu * ref.pue(mu, t_amb, pue_design)) / rho


def bands(mu, t_amb, rho, pue_design):
    mu = jnp.maximum(mu, 1e-3)
    dn = rho * pue_design / jnp.maximum(
        ref.meter_gain(mu, rho, t_amb, pue_design), 1e-3)
    up = rho * pue_design / jnp.maximum(
        gain_up(mu, rho, t_amb, pue_design), 1e-3)
    return (jnp.clip(dn, 0.0, jnp.maximum(mu - ref.MIN_RESIDUAL, 0.0)),
            jnp.clip(up, 0.0, jnp.maximum(1.0 - mu, 0.0)))


def hourly(ci, t_amb, mask, mw, pue_design, rho, mix, dt):
    valid = mask > 0
    lo = jnp.min(jnp.where(valid, ci, jnp.inf))
    hi = jnp.max(jnp.where(valid, ci, -jnp.inf))
    green = jnp.clip(1.0 - (ci - lo) / jnp.maximum(hi - lo, 1e-6), 0.0, 1.0)
    mus = _c(ref.MU_GRID, dt)
    J = (_c(ref.W_FFR, dt) * ref.q_ffr(mus[None, :], rho, t_amb[:, None],
                                       pue_design)
         + _c(ref.W_CFE, dt) * (green[:, None] * (mus / ref.MU_GRID[-1])
                                + (1.0 - green[:, None])
                                * (1.0 - mus / ref.MU_GRID[-1])))
    room = (mus - rho >= ref.MIN_RESIDUAL) & (mus + rho <= 1.0)
    J = jnp.where(room[None, :], J, -jnp.inf)
    mu_h = jnp.where(valid, mus[jnp.argmax(J, axis=1)], 0.0)
    rho_h = jnp.where(valid, jnp.broadcast_to(rho, ci.shape), 0.0)
    xs = jnp.sort(jnp.where(valid, ci, jnp.inf))
    n = jnp.sum(valid)
    pos = 0.5 * (n.astype(dt) - 1.0)
    i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, ci.shape[0] - 1)
    i1 = jnp.clip(i0 + 1, 0, n - 1)
    w = pos - i0.astype(dt)
    green_ci = xs[i0] * (1.0 - w) + xs[i1] * w
    load = jnp.clip(mu_h, 0.05, 1.0)
    it_w = load * mw * mask
    fac_w = load * ref.pue(load, t_amb, pue_design) * mw * mask
    is_green = ci <= green_ci
    clock_w = _c(ref.CLOCK_W, dt)[mix]
    thr = jnp.sum(ref.throughput_frac(clock_w, load) * mask)
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
        * _c(ref.TOKENS_PER_MW_S, dt)[mix] / 1e6)


# ---------------------------------------------------------------------------
# Frequency: OU deviation plus excursions; the droop activation
# ---------------------------------------------------------------------------


def frequency(seed_u32, n_seconds, events_per_day, max_events, dt):
    kw, ke = jax.random.split(jax.random.PRNGKey(seed_u32))
    kn, kt, ka, kr = jax.random.split(ke, 4)
    lam = jnp.asarray(events_per_day, jnp.float32) * n_seconds / 86_400.0
    n = jnp.minimum(jax.random.poisson(kn, lam), max_events)
    slot = jnp.arange(max_events)
    t_raw = jax.random.uniform(kt, (max_events,), minval=0.0,
                               maxval=float(n_seconds))
    order = jnp.argsort(jnp.where(slot < n, t_raw, jnp.inf))
    nadir = jax.random.uniform(ka, (max_events,),
                               minval=jnp.float32(NADIR_LO),
                               maxval=jnp.float32(NADIR_HI))
    rec = jax.random.uniform(kr, (max_events,), minval=60.0, maxval=600.0)
    t0s, nadirs, recs = (t_raw[order].astype(jnp.int32),
                         nadir[order].astype(dt), rec[order].astype(dt))
    g = jax.random.normal(kw, (n_seconds,)).astype(dt)
    phi = _c(np.exp(-1.0 / OU_TAU_S), dt)
    kick = OU_SIGMA_HZ * jnp.sqrt(1.0 - phi * phi)

    def step(x, gt):
        x = phi * x + kick * gt
        return x, x

    x0 = OU_SIGMA_HZ * g[0]
    _, rest = jax.lax.scan(step, x0, g[1:])
    f = 50.0 + jnp.concatenate([x0[None], rest])
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


def activation(f, dt):
    df = 50.0 - f
    a = jnp.maximum(jnp.abs(df) - _c(DEADBAND_HZ, dt), 0.0) / _c(
        FULL_HZ - DEADBAND_HZ, dt)
    return jnp.clip(jnp.sign(df) * a, -1.0, 1.0)


# ---------------------------------------------------------------------------
# Seconds tier: one site, one second of droop
# ---------------------------------------------------------------------------


def _caps(pred, env, prev, chips):
    scale = jnp.where(pred > env, env / jnp.maximum(pred, 1e-3), 1.0)
    share = prev * scale[:, None]
    room = jnp.maximum(env[:, None] - jnp.sum(share, 1, keepdims=True), 0.0)
    return jnp.clip(share + room / chips, ref.CAP_MIN, ref.CAP_MAX)


def second(s: dict, demand, act, in_hor, t, hp: dict, *, chips: int,
           chip_tdp: float, warmup_s: int, dt):
    """Advance one site by one second of droop.  ``hp``: this hour's mu,
    rho, t_amb, dn and up bands and declared power, plus pue_design,
    clock_w, the host means and the site scale.  Returns (state,
    (required, delivered) response per unit of design IT)."""
    n_hosts = demand.shape[0]
    design_host = chips * chip_tdp
    design_it = n_hosts * design_host
    mu = hp["mu"]
    band = jnp.where(act > 0, hp["dn"], hp["up"])
    frac = mu - band * act
    envelope = frac * design_it
    base = (hp["means"] + hp["scale"] * (demand - hp["means"])) * mu / 0.9
    load = jnp.clip(base * frac / jnp.maximum(mu, 1e-3), 0.0, 1.0)
    key, k1 = jax.random.split(s["key"])
    pred = jnp.sum(s["theta"] * s["hist"], axis=1) * design_host
    prev = jnp.maximum(s["chip_power"], ref.P_IDLE)
    caps = _caps(pred, jnp.full((n_hosts,), 1.0, dt) * (frac * design_host),
                 prev, chips)
    noise = 2.0 * hp["scale"] * jax.random.normal(
        k1, (n_hosts, chips)).astype(dt)
    target = jnp.minimum(ref.power_model(ref.F_NOMINAL, load[:, None], dt)
                         + noise, caps)
    deep = jnp.clip(frac * chip_tdp, ref.IDLE_FLOOR_W, caps)
    power = jnp.where(act > 0, jnp.minimum(target, deep), target)
    host_power = jnp.sum(power, axis=1)
    # RLS with forgetting on normalised host power
    u = host_power / design_host
    phi, P = s["hist"], s["P"]
    err = u - jnp.sum(s["theta"] * phi, axis=1)
    Pphi = jnp.sum(P * phi[:, None, :], axis=2)
    gain = Pphi / (ref.RLS_FORGET + jnp.sum(phi * Pphi, axis=1))[:, None]
    theta = s["theta"] + gain * err[:, None]
    Pn = (P - gain[:, :, None] * Pphi[:, None, :]) / ref.RLS_FORGET
    Pn = 0.5 * (Pn + jnp.swapaxes(Pn, 1, 2))
    tr = jnp.trace(Pn, axis1=1, axis2=2)
    Pn = Pn * jnp.minimum(1e4 * ref.RLS_ORDER / jnp.maximum(tr, 1e-9),
                          1.0)[:, None, None]
    warm = s["steps"] >= ref.RLS_ORDER
    theta = jnp.where(warm[:, None], theta, s["theta"])
    Pn = jnp.where(warm[:, None, None], Pn, P)
    hist = jnp.concatenate([u[:, None], phi[:, :-1]], axis=1)
    it = jnp.sum(host_power)
    L = it / design_it
    fac = it * ref.pue(L, hp["t_amb"], hp["pue_design"])
    g = in_hor.astype(dt)
    w = g * (t >= warmup_s).astype(dt)
    down = g * (act > 0).astype(dt)
    a = s["acc"]
    acc = dict(
        n_s=a["n_s"] + g, n_warm=a["n_warm"] + w,
        err=a["err"] + w * jnp.mean(jnp.abs(err) * design_host) / design_host,
        track=a["track"] + w * jnp.abs(it - envelope)
        / jnp.maximum(envelope, 1.0),
        load=a["load"] + g * L, fac=a["fac"] + g * fac / design_it,
        chip_mean=a["chip_mean"] + g * jnp.mean(power),
        chip_p95=a["chip_p95"] + g * jnp.percentile(power, 95.0),
        shed_s=a["shed_s"] + down,
        shed_it=a["shed_it"] + down * hp["dn"] * act,
        thr=a["thr"] + g * ref.throughput_frac(hp["clock_w"], L))
    new = dict(theta=theta, P=Pn, hist=hist, steps=s["steps"] + 1,
               chip_power=power, caps=caps, key=key, last_load=L,
               in_event=s["in_event"], hold=s["hold"], acc=acc)
    required = hp["rho"] * hp["pue_design"] * act
    return new, (required, hp["declared"] - fac / design_it)


# ---------------------------------------------------------------------------
# A whole scenario
# ---------------------------------------------------------------------------


def scenario(sc: dict, *, n_hosts: int, chips: int, chip_tdp: float,
             events_per_day: float, max_freq_events: int, warmup_s: int, dt):
    f = {k: (v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating) else v)
         for k, v in sc.items()}
    h = hourly(f["ci"], f["t_amb"], f["mask"], f["mw"], f["pue_design"],
               f["rho"], f["mix"], dt)
    h_max = f["ci"].shape[0]
    T = h_max * 3600
    valid_s = f["hours"] * 3600
    freq = frequency(
        f["event_seed"].astype(jnp.uint32) * jnp.uint32(100_003)
        + f["seed"].astype(jnp.uint32), T, events_per_day, max_freq_events,
        dt)
    act = activation(freq, dt)
    load_key, scan_key = jax.random.split(jax.random.PRNGKey(f["seed"]), 2)
    lp = ref.load_params(n_hosts, load_key, dt)
    demand = jax.vmap(lambda b: ref.hour_demand(lp, b, dt))(
        jnp.arange(h_max)).reshape(T, n_hosts)
    dn, up = bands(h["mu_h"], f["t_amb"], h["rho_h"], f["pue_design"])
    means = host_means(n_hosts, dt)
    decl = declared(h["mu_h"], f["t_amb"], f["pue_design"], means, chip_tdp)
    scale = jnp.minimum(jnp.sqrt(n_hosts * chips * chip_tdp
                                 / (f["mw"] * 1e6)), 1.0)
    clock_w = _c(ref.CLOCK_W, dt)[f["mix"]]
    t_all = jnp.arange(T, dtype=jnp.int32)

    def step(s, x):
        d, a, t = x
        hr = jnp.minimum(t // 3600, h_max - 1)
        hp = dict(mu=h["mu_h"][hr], rho=h["rho_h"][hr], t_amb=f["t_amb"][hr],
                  dn=dn[hr], up=up[hr], declared=decl[hr],
                  pue_design=f["pue_design"], clock_w=clock_w, means=means,
                  scale=scale)
        return second(s, d, a, t < valid_s, t, hp, chips=chips,
                      chip_tdp=chip_tdp, warmup_s=warmup_s, dt=dt)

    s, (req, dlv) = jax.lax.scan(
        step, ref.site_init(n_hosts, chips, scan_key, dt),
        (demand, act, t_all))
    # per 4-hour block, in site MW (seconds x MW)
    n_blk = -(-h_max // BLOCK_H)
    per_blk = BLOCK_H * 3600
    pad = n_blk * per_blk - T

    def blocks(x):
        return jnp.sum(jnp.pad(x, (0, pad)).reshape(n_blk, per_blk), axis=1)

    g = (t_all < valid_s).astype(dt)
    on = g * (act != 0).astype(dt)
    dnm = g * (act > 0).astype(dt)
    upm = g * (act < 0).astype(dt)
    mw = f["mw"]
    err_b = blocks(on * jnp.abs(dlv - req)) * mw
    active_b = blocks(on)
    hours_b = jnp.sum(jnp.pad(f["mask"], (0, n_blk * BLOCK_H - h_max))
                      .reshape(n_blk, BLOCK_H), axis=1)
    committed_h = h["rho_h"] * mw * f["pue_design"]
    com_mask = jnp.pad(committed_h * f["mask"], (0, n_blk * BLOCK_H - h_max))
    committed_b = jnp.sum(com_mask.reshape(n_blk, BLOCK_H), axis=1) \
        / jnp.maximum(hours_b, 1.0)
    valid_b = hours_b > 0
    mean_err = err_b / jnp.maximum(active_b, 1.0)
    ok_b = valid_b & ((active_b == 0) | (mean_err <= TRACKING_TOL
                                         * committed_b))
    price = _c(PRICE_EUR_MW_H, dt)
    capacity_b = price * jnp.sum(com_mask.reshape(n_blk, BLOCK_H), axis=1)
    capacity = price * jnp.sum(committed_h * f["mask"])
    penalty = jnp.sum(jnp.where(valid_b & ~ok_b, capacity_b, 0.0))
    a = s["acc"]
    n = jnp.maximum(a["n_s"], 1.0)
    nw = jnp.maximum(a["n_warm"], 1.0)
    tok_unit = mw * _c(ref.TOKENS_PER_MW_S, dt)[f["mix"]] / 1e6
    thr_ref = ref.throughput_frac(clock_w, _c(ref.MU_GRID[-1], dt))
    tokens = a["thr"] * tok_unit
    mwh = mw / 3600.0
    return dict(
        h,
        ar4_mae_norm=a["err"] / nw, tracking_err_mean=a["track"] / nw,
        chip_power_mean=a["chip_mean"] / n, chip_power_p95=a["chip_p95"] / n,
        it_mwh=a["load"] * mwh, fac_mwh=a["fac"] * mwh,
        shed_it_mwh=a["shed_it"] * mwh,
        active_s=jnp.sum(on).astype(jnp.int32),
        up_s=jnp.sum(upm).astype(jnp.int32),
        req_dn_mwh=jnp.sum(dnm * req) * mwh,
        req_up_mwh=-jnp.sum(upm * req) * mwh,
        dlv_dn_mwh=jnp.sum(dnm * dlv) * mwh,
        dlv_up_mwh=-jnp.sum(upm * dlv) * mwh,
        block_ok=ok_b, block_valid=valid_b,
        block_err_mw=jnp.where(valid_b, mean_err, 0.0),
        n_blocks=jnp.sum(valid_b).astype(jnp.int32),
        n_blocks_failed=jnp.sum(valid_b & ~ok_b).astype(jnp.int32),
        committed_mw=jnp.sum(committed_h * f["mask"])
        / jnp.maximum(jnp.sum(f["mask"]), 1.0),
        capacity_eur=capacity, penalty_eur=penalty,
        net_eur=capacity - penalty,
        thr_mean=a["thr"] / n, tokens_mtok=tokens,
        tokens_lost_mtok=a["n_s"] * thr_ref * tok_unit - tokens)


_STATIC = ("n_hosts", "chips", "chip_tdp", "events_per_day",
           "max_freq_events", "warmup_s", "dt")


@partial(jax.jit, static_argnames=_STATIC)
def _scenarios_jit(tab, **kw):
    with jax.default_matmul_precision("highest"):
        return jax.vmap(partial(scenario, **kw))(tab)


def table(specs, h_max: int) -> dict:
    """Host arrays of a list of scenario dicts (the keys of
    ``bench/reference.py``'s ``scenario_table``).  That table indexes only
    the triggered products; every scenario here sells FCR-CE, so its
    product column is filled with a placeholder and never read."""
    return ref.scenario_table([dict(s, product="FFR") for s in specs],
                              h_max)


def run_scenarios(specs, engine: dict, *, dt=jnp.float32, block: int = 512,
                  devices=None) -> dict:
    """Every scenario's outputs as host numpy, ``block`` scenarios per
    call, the calls' scenarios split over ``devices`` (default: the first
    device).  ``engine``: n_hosts, chips_per_host, chip_tdp,
    events_per_day, max_freq_events, warmup_s."""
    h_max = max(s["horizon_h"] for s in specs)
    kw = dict(n_hosts=engine["n_hosts"], chips=engine["chips_per_host"],
              chip_tdp=engine["chip_tdp"],
              events_per_day=engine["events_per_day"],
              max_freq_events=engine["max_freq_events"],
              warmup_s=engine["warmup_s"], dt=dt)
    devices = list(devices or jax.devices()[:1])
    run, size = partial(_scenarios_jit, **kw), block
    if len(devices) > 1:
        spec = jax.sharding.PartitionSpec("s")
        mesh = jax.sharding.Mesh(np.asarray(devices), ("s",))
        sharding = jax.sharding.NamedSharding(mesh, spec)
        run = jax.jit(jax.shard_map(lambda t: _scenarios_jit(t, **kw),
                                    mesh=mesh, in_specs=spec,
                                    out_specs=spec, check_vma=False))
        size = -(-min(block * len(devices), len(specs))
                 // len(devices)) * len(devices)
    parts = []
    for lo in range(0, len(specs), size):
        part = specs[lo:lo + size]
        tab = table(part, h_max)
        pad = size - len(part) if len(specs) > size or len(devices) > 1 \
            else 0
        if pad:
            tab = {k: np.concatenate([v, np.repeat(v[-1:], pad, 0)])
                   for k, v in tab.items()}
        if len(devices) > 1:
            tab = jax.device_put(tab, sharding)
        out = jax.tree.map(np.asarray, run(tab))
        parts.append({k: v[:len(part)] for k, v in out.items()})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
