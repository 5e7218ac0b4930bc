"""Unified three-tier rollout engine: ONE ``jit(vmap(lax.scan))`` per sweep.

Before this module the three tiers were three hand-stitched entry points:
the hourly schedule replayed through ``dispatch.replay_schedule``, the
twin's 1 Hz physics through ``twin.run_twin_batch``, and the reserve
detection/verification through ``reserve.reserve_replay_batch`` -- with
reserve verdicts evaluated against the schedule's quasi-static ``mu``
rather than the power the twin actually produced.  The engine composes
all of them into one functional, pytree-based simulation API:

  :class:`EngineConfig`   static fleet/physics/search knobs (hashable),
  :class:`EngineState`    the scan carry: Tier-2 RLS + plant + reserve
                          detection state + streaming aggregates,
  :func:`engine_init`     EngineConfig -> initial EngineState,
  :func:`engine_step`     one fused 1 Hz tick: reserve detection, duty
                          shed, Tier-2 predict/rebalance, plant, meter,
  :func:`engine_rollout`  ScenarioBatch -> one compiled pass: Tier-3
                          grid search (optionally price-aware), hourly
                          energy/carbon accounting, frequency synthesis,
                          the fused per-second scan, per-event verdicts,
                          and settlement.

Reserve delivery verdicts come from the twin's RLS-tracked per-second IT
power (the load the meter would actually see at the trigger second), not
the schedule's quasi-static ``mu``; the quasi-static verdicts are still
produced (``events_sched``) and match ``reserve_replay_batch`` exactly,
so the two diverge precisely when Tier-2 tracking error is nonzero.

``reduce="summary"`` keeps only running aggregates in the scan carry --
no ``(N, T, H)`` metric stacks -- so thousand-scenario sweeps scale in
batch size, not horizon.  ``reduce="full"`` additionally stacks the
per-second :class:`~repro.core.twin.TwinMetrics` (the parity surface the
tests pin against the hand-stitched composition).

Inputs are O(N*H) too: the rollout scan is hierarchical -- an outer scan
over hours, an inner scan over each hour's 3600 seconds -- and the outer
level generates its hour's demand block from the counter-based PRNG
(``twin.host_loads_block``, ``jax.random.fold_in`` on the scenario load
key and the hour index) and gathers the hourly tables once per hour, so
no ``(N, T, H)`` input buffer exists unless the caller passes a measured
``loads=`` override (validated up front; :func:`base_loads` materialises
the same trace -- identical PRNG bits, float path within 1 ulp).

A batch of a proportional product (``ScenarioBatch.proportional``, the
Continental FCR-CE) compiles a different scan, chosen statically from
the host-side batch: its per-second input is the signed droop activation
``a(t)`` in place of the trigger flag, its tick answers in both
directions (:func:`repro.core.twin.droop_tick`), and its hourly carry
sums the required and delivered meter response, which settle per 4-hour
block (:func:`_rollout_droop_one`).  Triggered batches compile the scan
they always did.

The scan carry is a flat pytree and every per-scenario input carries a
leading batch axis, which is what lets ``engine_rollout(mesh=...)`` wrap
the same vmapped rollout in ``shard_map`` over a ``"scenario"`` mesh
axis: the batch is auto-padded to a multiple of the device count
(replicating the last scenario), each device scans its slice, and the
outputs are sliced back -- single-device numbers to fp32 tolerance.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.core.dispatch as dispatch
import repro.core.plant as plant_lib
import repro.core.pue as pue_lib
import repro.core.reserve as reserve
import repro.core.tier3 as tier3_lib
import repro.core.twin as twin_lib
import repro.grid.frequency as frequency
import repro.grid.markets as markets
import repro.obs.telemetry as obs_tel
import repro.obs.trace as obs_trace
import repro.workload.model as workload_lib
from repro.grid.scenarios import ScenarioBatch, frequency_seeds, \
    masked_quantile, product_kind, scenario_chunk


@dataclass(frozen=True)
class EngineConfig:
    """Static knobs of the unified rollout (hashable: jit static arg).

    The simulated fleet is ``n_hosts x chips_per_host`` at ``chip_tdp``;
    per-scenario site size arrives traced via ``ScenarioBatch.mw`` and
    scales the fleet's normalised load to site MW, so one compiled rollout
    serves every MW level in the batch.
    """

    n_hosts: int = 4
    chips_per_host: int = 2
    chip_tdp: float = plant_lib.TDP
    pue_aware: bool = True
    # Tier-3: "batch" holds the committed band at ScenarioBatch.reserve_rho
    # (the band was sold ahead of time; only mu is free), "tier3" lets the
    # grid search choose (mu, rho) per hour.
    rho_mode: str = "batch"
    # settlement-revenue feedback into the grid search (price-aware points)
    price_aware: bool = False
    w_rev: float = tier3_lib.W_REV_DEFAULT
    # frequency synthesis / reserve replay
    events_per_day: float = tier3_lib.EVENTS_PER_DAY_DEFAULT
    e_max: int = 24
    max_freq_events: int = 64
    # workload-in-the-loop (repro.workload).  workload_weight is w_tok in
    # the Tier-3 objective: 0 keeps the selection graph bit-identical to
    # the throughput-blind engine (the parity guarantee); > 0 prices lost
    # training tokens against reserve revenue.  ckpt_cost_s is the
    # checkpoint+restore dead time one activation charges, and
    # step_transient_amp/step_period_s shape the step-synchronous power
    # wave modulating the demand inside the tick (0 = off, no graph
    # change).
    workload_weight: float = 0.0
    ckpt_cost_s: float = workload_lib.DEFAULT_GRID_CKPT_S
    step_transient_amp: float = 0.0
    step_period_s: float = workload_lib.STEP_PERIOD_S_DEFAULT
    # in-graph telemetry taps (repro.obs.telemetry): True threads a
    # second accumulator pytree through the hierarchical scan and adds a
    # "telemetry" dict to the rollout output (per-hour controller-health
    # moments, day-level fixed-bucket histograms, per-event
    # trigger-to-target response times vs the product budget -- all
    # O(N*H + N*B)).  Statically gated at the Python level, so False (the
    # default) is the pre-telemetry graph bit-for-bit (same pattern as
    # workload_weight=0).
    telemetry: bool = False
    # seconds-tier toggle: False runs the hourly tiers only (Tier-3 search
    # + schedule energy accounting), the E8 configuration
    with_seconds: bool = True
    warmup_s: int = 60          # RLS warm-up excluded from error metrics
    # scan unroll.  1 measures fastest on CPU for this op-heavy body: the
    # tick is dispatch-latency bound, and unrolling multiplies the body's
    # op count without enabling extra fusion across the RLS barriers
    # (unlike the tiny detection-only scan, where unroll=8 wins).
    unroll: int = 1

    def __post_init__(self):
        if self.rho_mode not in ("batch", "tier3"):
            raise ValueError(
                f"rho_mode must be 'batch' or 'tier3', got {self.rho_mode!r}")

    @property
    def n_chips(self) -> int:
        return self.n_hosts * self.chips_per_host

    @property
    def design_it_w(self) -> float:
        return self.n_chips * self.chip_tdp

    def twin_config(self, seconds: int) -> twin_lib.TwinConfig:
        return twin_lib.TwinConfig(
            n_hosts=self.n_hosts, chips_per_host=self.chips_per_host,
            chip_tdp=self.chip_tdp, pue_aware=self.pue_aware,
            seconds=seconds, step_transient_amp=self.step_transient_amp,
            step_period_s=self.step_period_s)


class EngineAccum(NamedTuple):
    """Streaming aggregates carried through the scan (reduce="summary")."""

    n_s: jax.Array          # valid (in-horizon) seconds
    n_warm: jax.Array       # valid seconds past the RLS warm-up
    err: jax.Array          # sum of per-tick mean |AR4 err| / design_host
    track: jax.Array        # sum of tracking_err past warm-up
    load: jax.Array         # sum of cluster L = it / design (per-unit)
    fac: jax.Array          # sum of L * PUE(L) (per-unit meter draw)
    chip_mean: jax.Array    # sum of per-tick chip power mean (W)
    chip_p95: jax.Array     # sum of per-tick chip power p95 (W)
    shed_s: jax.Array       # seconds spent shedding for the reserve
    shed_it: jax.Array      # sum of armed rho_it over shed seconds
    thr: jax.Array          # sum of workload throughput fraction g(L)


class EngineState(NamedTuple):
    """The fused scan carry: twin + reserve detection + aggregates."""

    rls: object             # ar4.RLSState
    chip_power: jax.Array   # (H, C) W
    caps: jax.Array         # (H, C) W
    key: jax.Array          # plant-noise PRNG key
    last_load: jax.Array    # previous second's cluster L (pre-trigger power)
    in_event: jax.Array     # reserve detection: inside a held activation
    hold: jax.Array         # reserve detection: sustain countdown (s)
    acc: EngineAccum


class EngineParams(NamedTuple):
    """Per-scenario traced tables the step gathers from by hour."""

    mu_h: jax.Array         # (Hm,) operating fraction
    rho_h: jax.Array        # (Hm,) committed band
    t_amb_h: jax.Array      # (Hm,) ambient degC
    rho_it_h: jax.Array     # (Hm,) armed IT-side band (quasi-static table)
    min_dur_i: jax.Array    # scalar int32 product sustain window
    pue_design: jax.Array   # scalar
    clock_w: jax.Array      # scalar workload-mix clock weight (CLOCK_W)


class EngineSecond(NamedTuple):
    """Per-second scan outputs needed beyond the carry."""

    trig: jax.Array         # bool: a reserve event triggered this second
    shed: jax.Array         # bool: the reserve shed is being served
    load: jax.Array         # cluster L at the START of the second (pre-shed)


def engine_init(cfg: EngineConfig, key) -> EngineState:
    """Initial carry for one scenario's fused scan."""
    rls, chip_power, caps, key = twin_lib.twin_carry_init(
        cfg.n_hosts, cfg.chips_per_host, key)
    in_ev, hold = reserve.detection_init()
    z = jnp.zeros((), jnp.float32)
    return EngineState(
        rls=rls, chip_power=chip_power, caps=caps, key=key,
        last_load=jnp.asarray(plant_lib.P_IDLE / cfg.chip_tdp, jnp.float32),
        in_event=in_ev, hold=hold,
        acc=EngineAccum(*([z] * len(EngineAccum._fields))),
    )


class HourParams(NamedTuple):
    """One hour's scalars, gathered from :class:`EngineParams` ONCE per
    hour by the rollout's outer scan level (not once per tick)."""

    mu: jax.Array
    rho: jax.Array
    t_amb: jax.Array
    rho_it: jax.Array
    min_dur_i: jax.Array
    pue_design: jax.Array
    clock_w: jax.Array


def _hour_params(params: EngineParams, hour) -> HourParams:
    h_max = params.mu_h.shape[-1]
    hour = jnp.minimum(hour, h_max - 1)
    return HourParams(
        mu=params.mu_h[hour], rho=params.rho_h[hour],
        t_amb=params.t_amb_h[hour], rho_it=params.rho_it_h[hour],
        min_dur_i=params.min_dur_i, pue_design=params.pue_design,
        clock_w=params.clock_w)


def _demand(cfg: EngineConfig, base_load, mu, t):
    """The hosts' demand at the hour's operating fraction."""
    load_h = base_load * mu / 0.9
    if cfg.step_transient_amp:
        # step-synchronous power wave (EasyRider): gated on the STATIC
        # amplitude so the default-0 graph is unchanged (the parity path)
        load_h = jnp.clip(
            load_h * workload_lib.step_transient(
                t, cfg.step_period_s, cfg.step_transient_amp), 0.0, 1.0)
    return load_h


def _accumulate(cfg: EngineConfig, a: EngineAccum, m, in_hor, t, shed,
                shed_it_rate, clock_w):
    """(L, running sums) after one tick: ``shed`` marks a second of shed
    and ``shed_it_rate`` the IT-side band it shed."""
    L = m.it_power / cfg.design_it_w
    g = in_hor.astype(jnp.float32)
    w = g * (t >= cfg.warmup_s)
    design_host = cfg.chips_per_host * cfg.chip_tdp
    return L, EngineAccum(
        n_s=a.n_s + g,
        n_warm=a.n_warm + w,
        err=a.err + w * jnp.mean(m.ar4_abs_err) / design_host,
        track=a.track + w * m.tracking_err,
        load=a.load + g * L,
        fac=a.fac + g * m.facility_power / cfg.design_it_w,
        chip_mean=a.chip_mean + g * m.chip_power_mean,
        chip_p95=a.chip_p95 + g * m.chip_power_p95,
        shed_s=a.shed_s + shed.astype(jnp.float32),
        shed_it=a.shed_it + shed_it_rate * shed,
        # realised workload throughput at this second's cluster power
        # fraction -- the per-chip budget the fleet actually ran at --
        # through the shared DVFS/duty-cycle curve
        thr=a.thr + g * workload_lib.throughput_frac(clock_w, L),
    )


def _engine_tick(cfg: EngineConfig, hp: HourParams, state: EngineState, xs):
    """The fused 1 Hz tick body with the hour's scalars already gathered."""
    base_load, below, in_hor, t = xs
    (in_ev, hold), trig, shed = reserve.detection_step(
        (state.in_event, state.hold), below, in_hor, hp.min_dur_i)

    load_h = _demand(cfg, base_load, hp.mu, t)
    carry = (state.rls, state.chip_power, state.caps, state.key)
    (rls, chip_power, caps, key), m = twin_lib.twin_tick(
        cfg.n_hosts, cfg.chips_per_host, cfg.chip_tdp, hp.pue_design,
        carry, load_h, hp.mu, hp.rho, shed, hp.t_amb)

    L, acc = _accumulate(cfg, state.acc, m, in_hor, t, shed, hp.rho_it,
                         hp.clock_w)
    sec = EngineSecond(trig=trig, shed=shed, load=state.last_load)
    new = EngineState(rls=rls, chip_power=chip_power, caps=caps, key=key,
                      last_load=L, in_event=in_ev, hold=hold, acc=acc)
    return new, (sec, m)


class DroopHour(NamedTuple):
    """One hour's scalars of a proportional product's tick.  In the
    rollout's table the hourly leaves are (Hm,) and the rest scalars;
    the outer scan takes each hour's row once."""

    mu: jax.Array
    rho: jax.Array
    t_amb: jax.Array
    band_dn: jax.Array      # IT-side band under-frequency (a > 0)
    band_up: jax.Array      # IT-side band over-frequency (a < 0)
    declared: jax.Array     # declared facility power, per unit design IT
    pue_design: jax.Array
    clock_w: jax.Array
    scale: jax.Array        # twin.site_scale of the scenario's site


class FcrHour(NamedTuple):
    """One hour's sums of a proportional product's response, per unit of
    design IT power times seconds (the droop scan's inner carry, reset
    each hour and stacked by the outer scan)."""

    req_dn: jax.Array       # required meter response, under-frequency
    req_up: jax.Array       # |required| over-frequency
    dlv_dn: jax.Array       # delivered (declared - meter), under
    dlv_up: jax.Array       # |delivered| over-frequency
    abs_err: jax.Array      # |delivered - required| over active seconds
    active_s: jax.Array     # seconds with a != 0
    up_s: jax.Array         # seconds with a < 0


def _fcr_hour_init() -> FcrHour:
    z = jnp.zeros((), jnp.float32)
    return FcrHour(*([z] * len(FcrHour._fields)))


def _declared_fac(cfg: EngineConfig, mu_h, t_amb, pue_design):
    """(Hm,) facility power, per unit of design IT, that a site selling a
    proportional product declares as its baseline for each hour: at the
    hour's mu, every host's long-run mean demand
    (``twin.host_mean_demand``, scaled as :func:`_demand` scales it)
    through the power model, each chip held to its share of the envelope,
    then the meter.  It needs only the schedule and the workload's
    archetypes, so it is known before the day starts."""
    mean = twin_lib.host_mean_demand(cfg.n_hosts)
    load = jnp.clip(mean * mu_h[:, None] / 0.9, 0.0, 1.0)       # (Hm, H)
    cap = jnp.clip(mu_h[:, None] * cfg.chip_tdp, plant_lib.CAP_MIN,
                   plant_lib.CAP_MAX)
    chip = jnp.minimum(plant_lib.power_model(plant_lib.F_NOMINAL, load), cap)
    it = jnp.mean(chip, axis=-1) / cfg.chip_tdp
    return it * pue_lib.pue(it, t_amb, pue_design=pue_design)


def _droop_tick(cfg: EngineConfig, hp: DroopHour, state: EngineState,
                fh: FcrHour, xs):
    """The 1 Hz tick of a proportional product: the site's demand, the
    droop twin tick, the running sums, and the hour's response sums.
    Returns (state, fh, metrics of the second)."""
    base_load, act, in_hor, t = xs
    demand = twin_lib.site_demand(
        base_load, twin_lib.host_mean_demand(cfg.n_hosts), hp.scale)
    load_h = _demand(cfg, demand, hp.mu, t)
    carry = (state.rls, state.chip_power, state.caps, state.key)
    (rls, chip_power, caps, key), m = twin_lib.droop_tick(
        cfg.n_hosts, cfg.chips_per_host, cfg.chip_tdp, hp.pue_design,
        carry, load_h, hp.mu, hp.band_dn, hp.band_up, act, hp.t_amb,
        hp.scale)
    shed = (act > 0) & in_hor
    L, acc = _accumulate(cfg, state.acc, m, in_hor, t, shed,
                         hp.band_dn * act, hp.clock_w)
    with jax.named_scope("engine.fcr_blocks"):
        g = in_hor.astype(jnp.float32)
        dn = g * (act > 0)
        up = g * (act < 0)
        on = dn + up
        req = hp.rho * hp.pue_design * act
        dlv = hp.declared - m.facility_power / cfg.design_it_w
        fh = FcrHour(req_dn=fh.req_dn + dn * req,
                     req_up=fh.req_up - up * req,
                     dlv_dn=fh.dlv_dn + dn * dlv,
                     dlv_up=fh.dlv_up - up * dlv,
                     abs_err=fh.abs_err + on * jnp.abs(dlv - req),
                     active_s=fh.active_s + on,
                     up_s=fh.up_s + up)
    new = state._replace(rls=rls, chip_power=chip_power, caps=caps, key=key,
                         last_load=L, acc=acc)
    return new, fh, m


def engine_step(cfg: EngineConfig, params: EngineParams, state: EngineState,
                xs):
    """One fused 1 Hz tick.

    xs = (base_load (H,), below bool, in_hor bool, t int32): the per-host
    demand archetype row (unscaled), the frequency-below-trigger flag, the
    ragged-horizon gate, and the second index.  Order of operations:

      1. reserve detection state machine (identical to the standalone
         ``reserve.reserve_replay`` scan -- event times match exactly),
      2. the twin tick with the detected shed driving the FFR duty shed
         (the activation actually takes power out of the plant),
      3. streaming aggregate update.

    Returns (state, (EngineSecond, TwinMetrics)).  The rollout's own scan
    walks hours and gathers the hourly tables once per hour
    (:func:`_hour_params`); this per-tick entry point gathers them from
    ``t`` and runs the identical tick body.
    """
    t = xs[3]
    return _engine_tick(cfg, _hour_params(params, t // 3600), state, xs)


# ---------------------------------------------------------------------------
# Per-scenario rollout (vmapped below)
# ---------------------------------------------------------------------------


def _hourly_one(cfg: EngineConfig, ci, t_amb, mask, mw, pue_design,
                product_idx, rho_batch, mix_idx, ops=None,
                symmetric: bool = False) -> dict:
    """Tier-3 grid search + hourly schedule energy/carbon accounting.

    ``ops`` overrides the in-graph grid search with externally committed
    hourly trajectories: a ``(mu_h, rho_h)`` pair of (H_max,) arrays (the
    differentiable bidder's output replayed through the real settlement).
    The ``None`` default is a static Python branch, so every existing
    caller keeps the exact pre-override graph.  ``symmetric`` (static)
    searches for a proportional product (``tier3.headroom_ok``).
    """
    clock_w = jnp.asarray(workload_lib.CLOCK_W)[mix_idx]
    if ops is None:
        green = tier3_lib.greenness_from_ci(ci, mask)
        w_rev = cfg.w_rev if cfg.price_aware else 0.0
        op = tier3_lib.select_operating_points(
            green, t_amb, pue_aware=cfg.pue_aware, pue_design=pue_design,
            weights=(tier3_lib.W_FFR, tier3_lib.W_CFE, w_rev,
                     cfg.workload_weight),
            product_idx=product_idx, events_per_day=cfg.events_per_day,
            rho_fixed=rho_batch, clock_w=clock_w, ckpt_cost_s=cfg.ckpt_cost_s,
            use_revenue=cfg.price_aware,
            fix_rho=(cfg.rho_mode == "batch"),
            use_workload=(cfg.workload_weight != 0.0),
            symmetric=symmetric)
        mu_sel, rho_sel = op.mu, op.rho
    else:
        mu_sel, rho_sel = ops
    mu_h = jnp.where(mask > 0, mu_sel, 0.0)
    rho_h = jnp.where(mask > 0, rho_sel, 0.0)
    green_ci = masked_quantile(ci, mask, 50.0)
    energy = dispatch.replay_schedule(mu_h, ci, t_amb, mask,
                                      pue_design=pue_design,
                                      green_ci=green_ci, design_w=mw,
                                      clock_w=clock_w)
    hv = jnp.maximum(jnp.sum(mask), 1.0)
    tok_rate = jnp.asarray(workload_lib.TOKENS_PER_MW_S)[mix_idx]
    return dict(
        mu_h=mu_h, rho_h=rho_h,
        mean_mu=jnp.sum(mu_h * mask) / hv,
        mean_rho=jnp.sum(rho_h * mask) / hv,
        sched_it_mwh=energy["it"],
        sched_fac_mwh=energy["fac"],
        sched_co2_t=energy["co2"] / 1000.0,
        sched_co2_it_t=energy["co2_it"] / 1000.0,
        sched_cfe_fac_mwh=energy["cfe_fac"],
        cfe_mu=energy["cfe_mu"],
        # quasi-static workload accounting: full-rate-equivalent schedule
        # hours -> millions of tokens at the mix's site rate
        sched_tokens_mtok=energy["thr"] * 3600.0 * mw * tok_rate / 1e6,
    )


def _scan_inputs(cfg: EngineConfig, per_second, valid_s, base_loads,
                 load_key):
    """The hierarchical scan's inputs: (load synthesis constants or None,
    xs), with the (T,) per-second input, the horizon gate and (with a
    ``base_loads`` override) the demand rows blocked into (B, K) hours."""
    T = per_second.shape[-1]
    K = twin_lib.LOAD_BLOCK_S
    B = T // K
    flags_b = per_second.reshape(B, K)
    in_hor_b = (jnp.arange(T, dtype=jnp.int32) < valid_s).reshape(B, K)
    hours_idx = jnp.arange(B, dtype=jnp.int32)
    lp = (twin_lib.host_load_params(cfg.n_hosts, load_key)
          if base_loads is None else None)
    xs = ((flags_b, in_hor_b, hours_idx) if base_loads is None else
          (base_loads.reshape(B, K, -1), flags_b, in_hor_b, hours_idx))
    return lp, xs


def _hour_rows(lp, xb):
    """One hour of the scan inputs: (demand rows, per-second input,
    horizon gate, hour index); the rows come from the counter-based PRNG
    unless the caller passed them."""
    if lp is not None:
        flags_r, in_r, b = xb
        return twin_lib.host_loads_block(lp, b), flags_r, in_r, b
    return xb


def _site_outputs(acc: EngineAccum, mw, mix_idx, clock_w):
    """The twin's and the workload's outputs of a rollout's running sums
    (streaming aggregates; site-MW energies; millions of tokens), shared
    by both scans.  Earned tokens integrate the realised per-second
    throughput; the reference runs every valid second at the top of the
    mu grid.  Returns (outputs, reference throughput, Mtok per
    throughput-second)."""
    n = jnp.maximum(acc.n_s, 1.0)
    nw = jnp.maximum(acc.n_warm, 1.0)
    tok_rate = jnp.asarray(workload_lib.TOKENS_PER_MW_S)[mix_idx]
    thr_ref = workload_lib.throughput_frac(
        clock_w, float(tier3_lib.MU_GRID[-1]))
    tok_unit = mw * tok_rate / 1e6
    return dict(
        ar4_mae_norm=acc.err / nw,
        tracking_err_mean=acc.track / nw,
        chip_power_mean=acc.chip_mean / n,
        chip_power_p95=acc.chip_p95 / n,
        it_mwh=acc.load * mw / 3600.0,
        fac_mwh=acc.fac * mw / 3600.0,
        shed_it_mwh=acc.shed_it * mw / 3600.0,
        thr_mean=acc.thr / n,
        tokens_mtok=acc.thr * tok_unit,
    ), thr_ref, tok_unit


def _rollout_one(cfg: EngineConfig, reduce: str, ci, t_amb, mask, hours,
                 mw, pue_design, product_idx, rho_batch, mix_idx, freq,
                 base_loads, load_key, key, ops=None) -> dict:
    out = _hourly_one(cfg, ci, t_amb, mask, mw, pue_design, product_idx,
                      rho_batch, mix_idx, ops)
    mu_h, rho_h = out["mu_h"], out["rho_h"]
    clock_w = jnp.asarray(workload_lib.CLOCK_W)[mix_idx]
    h_max = ci.shape[-1]
    T = freq.shape[-1]
    valid_s = jnp.asarray(hours, jnp.int32) * 3600

    # hoisted quasi-static activation physics (the reserve_replay tables):
    # used for the armed-band energy accounting and the schedule-side
    # verdicts the parity tests pin against reserve_replay_batch
    vh = tier3_lib.event_verdict(mu_h, t_amb, rho_h, product_idx,
                                 pue_design, pue_aware=cfg.pue_aware)
    min_dur_f = jnp.asarray(markets.MIN_DURATION_S)[product_idx]
    trig_hz = jnp.asarray(markets.TRIGGER_HZ)[product_idx]

    params = EngineParams(mu_h=mu_h, rho_h=rho_h, t_amb_h=t_amb,
                          rho_it_h=vh["rho_it"],
                          min_dur_i=min_dur_f.astype(jnp.int32),
                          pue_design=pue_design, clock_w=clock_w)
    # --- the fused scan, walked hierarchically: an outer scan over hours
    # and an inner scan over the hour's LOAD_BLOCK_S (= 3600) seconds.
    # The outer level gathers the hourly tables once per hour and -- when
    # no loads buffer was passed -- synthesises the hour's (K, H) demand
    # block from the counter-based PRNG (one fold_in + one vectorised
    # normal per hour, ~30 % cheaper than per-tick draws inside the
    # body), so peak input memory stays O(H) per scenario per hour.
    K = twin_lib.LOAD_BLOCK_S
    lp, xs = _scan_inputs(cfg, freq < trig_hz, valid_s, base_loads, load_key)

    design_host = cfg.chips_per_host * cfg.chip_tdp

    def hour_body(state, xb):
        loads_r, below_r, in_r, b = _hour_rows(lp, xb)
        hp = _hour_params(params, b)
        t_row = b * K + jnp.arange(K, dtype=jnp.int32)

        def tick(carry, x):
            st = carry[0] if cfg.telemetry else carry
            st, (sec, m) = _engine_tick(cfg, hp, st, x)
            out_t = (sec, m) if reduce == "full" else sec
            if cfg.telemetry:
                # telemetry rides a per-hour accumulator in the inner
                # carry (reset each hour, emitted as OUTER ys below):
                # pure elementwise sums off the tick's loop-carried
                # critical path, fused by XLA into the engine's own
                # accumulator update -- no per-tick buffer store.  Gated
                # on the STATIC cfg.telemetry flag so the default-False
                # scan body is the pre-telemetry body unchanged.
                _, _, in_t, t_t = x
                g_t = in_t.astype(jnp.float32)
                ta = obs_tel.accum_update(
                    carry[1], state=st, m=m, g=g_t,
                    w=g_t * (t_t >= cfg.warmup_s))
                return (st, ta), out_t
            return st, out_t

        xs_r = (loads_r, below_r, in_r, t_row)
        if cfg.telemetry:
            (state, ta), ys = jax.lax.scan(
                tick, (state, obs_tel.accum_init()), xs_r,
                unroll=cfg.unroll)
            # the hour's telemetry sums leave through the outer ys: the
            # outer scan stacks them to (B, ...) -- never (T, ...)
            return state, (ys, ta)
        return jax.lax.scan(tick, state, xs_r, unroll=cfg.unroll)

    state, ys = jax.lax.scan(hour_body, engine_init(cfg, key), xs)
    if cfg.telemetry:
        ys, tel_h = ys
    # flatten the (B, K, ...) stacks back to a seconds axis
    ys = jax.tree.map(lambda a: a.reshape((T,) + a.shape[2:]), ys)
    sec, metrics = ys if reduce == "full" else (ys, None)

    # --- per-event verdicts -------------------------------------------------
    t_ev, valid = reserve.event_times(sec.trig, cfg.e_max)
    hour_ev = jnp.minimum(t_ev // 3600, h_max - 1)
    # schedule-side (quasi-static) verdicts: exact reserve_replay parity
    vq = {k: x[hour_ev] for k, x in vh.items()}
    events_sched = reserve.assemble_events(vq, t_ev, valid, min_dur_f,
                                           valid_s, mw)
    # twin-coupled verdicts: the pre-trigger operating point is the twin's
    # RLS-tracked per-second IT power, not the schedule's quasi-static mu
    l_ev = sec.load[jnp.clip(t_ev, 0, T - 1)]
    vt = tier3_lib.event_verdict(l_ev, t_amb[hour_ev], rho_h[hour_ev],
                                 product_idx, pue_design,
                                 pue_aware=cfg.pue_aware)
    events = reserve.assemble_events(vt, t_ev, valid, min_dur_f, valid_s, mw)

    # --- settlement (capacity revenue vs clawback, hourly committed band;
    #     same rule as settle_reserve, with the band gathered per event hour)
    price = jnp.asarray(markets.CAPACITY_PRICE_EUR_MW_H)[product_idx]
    committed_h = rho_h * mw * pue_design                  # (Hm,) meter MW
    capacity_eur = price * jnp.sum(committed_h * mask)
    penalty_eur = reserve.event_clawback(
        events, price * committed_h[hour_ev] * tier3_lib.PENALTY_WINDOW_H)

    # --- workload settlement: each event additionally charges the
    #     checkpoint+restore dead time at the reference rate.
    acc = state.acc
    site, thr_ref, tok_unit = _site_outputs(acc, mw, mix_idx, clock_w)
    n_events_f = jnp.sum(valid).astype(jnp.float32)
    tokens_ckpt_mtok = n_events_f * cfg.ckpt_cost_s * thr_ref * tok_unit
    tokens_ref_mtok = acc.n_s * thr_ref * tok_unit

    out.update(
        site,
        # reserve replay + settlement
        events=events,
        events_sched=events_sched,
        n_events=jnp.sum(valid).astype(jnp.int32),
        active_s=acc.shed_s.astype(jnp.int32),
        committed_mw=jnp.sum(committed_h * mask)
        / jnp.maximum(jnp.sum(mask), 1.0),
        capacity_eur=capacity_eur,
        penalty_eur=penalty_eur,
        net_eur=capacity_eur - penalty_eur,
        n_compliant=jnp.sum(valid & events.compliant).astype(jnp.int32),
        # workload settlement (millions of tokens)
        tokens_ckpt_mtok=tokens_ckpt_mtok,
        tokens_lost_mtok=tokens_ref_mtok - site["tokens_mtok"]
        + tokens_ckpt_mtok,
    )
    if cfg.telemetry:
        out["telemetry"] = obs_tel.finalize(
            tel_h, design_host=design_host, events=events,
            budget_ms=jnp.asarray(markets.BUDGET_MS)[product_idx],
            load_sec=sec.load, valid_s=valid_s, warmup_s=cfg.warmup_s,
            last_load=state.last_load)
    if reduce == "full":
        out["metrics"] = metrics
        out["trig"] = sec.trig
        out["shed"] = sec.shed
        out["load_sec"] = sec.load
    return out


def _rollout_droop_one(cfg: EngineConfig, reduce: str, ci, t_amb, mask,
                       hours, mw, pue_design, product_idx, rho_batch,
                       mix_idx, freq, base_loads, load_key, key,
                       ops=None) -> dict:
    """One scenario of a proportional product (FCR-CE): the symmetric
    Tier-3 search, the droop scan, and per-block verdicts and settlement.

    Every second the site answers ``a(t)`` (``reserve.droop_activation``
    under ``markets.DROOP``); the required response is ``rho * MW *
    PUE_design * a`` at the meter, the delivered one the hour's declared
    baseline (:func:`_declared_fac`) less the meter.  The simulated hosts
    stand for the site's population of hosts (``twin.site_demand``,
    ``twin.site_scale``).  The response sums settle per block of
    ``DROOP.block_h`` hours: a block whose mean |delivered - required|
    over its active seconds passes ``tracking_tol`` times the committed
    meter MW forfeits the block's capacity payment.  Summary mode emits no
    per-second output.
    """
    droop = markets.DROOP
    out = _hourly_one(cfg, ci, t_amb, mask, mw, pue_design, product_idx,
                      rho_batch, mix_idx, ops, symmetric=True)
    mu_h, rho_h = out["mu_h"], out["rho_h"]
    clock_w = jnp.asarray(workload_lib.CLOCK_W)[mix_idx]
    T = freq.shape[-1]
    h_max = mu_h.shape[-1]
    valid_s = jnp.asarray(hours, jnp.int32) * 3600
    band_dn, band_up = tier3_lib.droop_bands(
        mu_h, t_amb, rho_h, pue_design, pue_aware=cfg.pue_aware)
    declared = _declared_fac(cfg, mu_h, t_amb, pue_design)
    table = DroopHour(
        mu=mu_h, rho=rho_h, t_amb=t_amb, band_dn=band_dn, band_up=band_up,
        declared=declared, pue_design=pue_design, clock_w=clock_w,
        scale=twin_lib.site_scale(cfg.n_hosts * cfg.chips_per_host,
                                  cfg.chip_tdp, mw))
    K = twin_lib.LOAD_BLOCK_S
    with jax.named_scope("engine.droop"):
        act = reserve.droop_activation(freq, droop.deadband_hz,
                                       droop.full_activation_hz)
    lp, xs = _scan_inputs(cfg, act, valid_s, base_loads, load_key)

    def hour_body(state, xb):
        loads_r, act_r, in_r, b = _hour_rows(lp, xb)
        hr = jnp.minimum(b, h_max - 1)
        hp = jax.tree.map(lambda x: x[hr] if x.ndim else x, table)
        t_row = b * K + jnp.arange(K, dtype=jnp.int32)

        def tick(carry, x):
            st, fh, m = _droop_tick(cfg, hp, *carry, x)
            return (st, fh), (m if reduce == "full" else None)

        (state, fh), ys = jax.lax.scan(
            tick, (state, _fcr_hour_init()), (loads_r, act_r, in_r, t_row),
            unroll=cfg.unroll)
        return state, (ys, fh)

    state, (ys, fh) = jax.lax.scan(hour_body, engine_init(cfg, key), xs)

    with jax.named_scope("engine.fcr_blocks"):
        price = jnp.asarray(markets.CAPACITY_PRICE_EUR_MW_H)[product_idx]
        committed_h = rho_h * mw * pue_design              # (Hm,) meter MW
        capacity_h = price * committed_h * mask

        def blocks(x):
            return reserve.to_blocks(x, droop.block_h)

        hours_b = blocks(mask)
        valid_b = hours_b > 0
        committed_b = blocks(committed_h * mask) / jnp.maximum(hours_b, 1.0)
        err_b = blocks(fh.abs_err) * mw                    # MW s
        active_b = blocks(fh.active_s)
        ok_b = reserve.block_verdicts(err_b, active_b, committed_b,
                                      droop.tracking_tol)
        capacity_eur = jnp.sum(capacity_h)
        penalty_eur = reserve.block_clawback(ok_b, valid_b,
                                             blocks(capacity_h))

    acc = state.acc
    site, thr_ref, tok_unit = _site_outputs(acc, mw, mix_idx, clock_w)
    mwh = mw / 3600.0                      # per-unit seconds -> site MWh
    out.update(
        site,
        # the droop's response and its settlement
        declared_mw_h=declared * mw * mask,
        active_s=jnp.sum(fh.active_s).astype(jnp.int32),
        up_s=jnp.sum(fh.up_s).astype(jnp.int32),
        req_dn_mwh=jnp.sum(fh.req_dn) * mwh,
        req_up_mwh=jnp.sum(fh.req_up) * mwh,
        dlv_dn_mwh=jnp.sum(fh.dlv_dn) * mwh,
        dlv_up_mwh=jnp.sum(fh.dlv_up) * mwh,
        block_ok=ok_b & valid_b,
        block_valid=valid_b,
        block_err_mw=jnp.where(valid_b,
                               err_b / jnp.maximum(active_b, 1.0), 0.0),
        n_blocks=jnp.sum(valid_b).astype(jnp.int32),
        n_blocks_failed=jnp.sum(valid_b & ~ok_b).astype(jnp.int32),
        committed_mw=jnp.sum(committed_h * mask)
        / jnp.maximum(jnp.sum(mask), 1.0),
        capacity_eur=capacity_eur,
        penalty_eur=penalty_eur,
        net_eur=capacity_eur - penalty_eur,
        # workload: the droop duty-scales, it never checkpoints
        tokens_lost_mtok=acc.n_s * thr_ref * tok_unit - site["tokens_mtok"],
    )
    if reduce == "full":
        out.update(metrics=jax.tree.map(
            lambda a: a.reshape((T,) + a.shape[2:]), ys), act=act)
    return out


# the device-computed counts of a proportional rollout, published to the
# tracer's metrics by publish_fcr_counters: counter name -> output key
FCR_COUNTERS = {"fcr.active_s": "active_s", "fcr.up_s": "up_s",
                "fcr.blocks": "n_blocks",
                "fcr.blocks_failed": "n_blocks_failed"}


def publish_fcr_counters(out: dict) -> dict:
    """Add the FCR counts of a proportional rollout's (or finalized
    sweep's) outputs to ``repro.obs.trace.metrics``.  The counts are
    computed on the device inside the rollout; reading them is this
    call's one host sync, which is why the rollout does not make it.
    Returns the added values."""
    got = {name: float(np.sum(np.asarray(out[k])))
           for name, k in FCR_COUNTERS.items()}
    for name, v in got.items():
        obs_trace.metrics.inc(name, v)
    return got


def _engine_seconds_vmapped(cfg: EngineConfig, reduce: str,
                            batch: ScenarioBatch, freq, base_loads,
                            load_keys, scan_keys, ops=None) -> dict:
    # ops=None is an empty pytree, so the uniform in_axes=0 maps it (and a
    # None base_loads) trivially; an (N, H_max) ops pair maps per scenario.
    fn = partial(_rollout_droop_one if batch.proportional else _rollout_one,
                 cfg, reduce)
    return jax.vmap(fn)(batch.ci, batch.t_amb, batch.mask, batch.hours,
                        batch.mw, batch.pue_design, batch.product_idx,
                        batch.reserve_rho, batch.mix_idx, freq, base_loads,
                        load_keys, scan_keys, ops)


@partial(jax.jit, static_argnames=("cfg", "reduce"))
def _engine_seconds_jit(cfg: EngineConfig, reduce: str, batch: ScenarioBatch,
                        freq, base_loads, load_keys, scan_keys,
                        ops=None) -> dict:
    return _engine_seconds_vmapped(cfg, reduce, batch, freq, base_loads,
                                   load_keys, scan_keys, ops)


def _engine_hourly_vmapped(cfg: EngineConfig, batch: ScenarioBatch,
                           ops=None) -> dict:
    fn = partial(_hourly_one, cfg, symmetric=batch.proportional)
    return jax.vmap(fn)(batch.ci, batch.t_amb, batch.mask, batch.mw,
                        batch.pue_design, batch.product_idx,
                        batch.reserve_rho, batch.mix_idx, ops)


@partial(jax.jit, static_argnames=("cfg",))
def _engine_hourly_jit(cfg: EngineConfig, batch: ScenarioBatch,
                       ops=None) -> dict:
    return _engine_hourly_vmapped(cfg, batch, ops)


# ---------------------------------------------------------------------------
# Device-sharded sweep: shard_map over a "scenario" mesh axis
# ---------------------------------------------------------------------------

_SCENARIO_AXIS = "scenario"


def _resolve_mesh(mesh):
    """mesh= argument -> a validated Mesh with a "scenario" axis.

    Strings ("auto" | "local" | "distributed") resolve through the single
    mesh-resolution layer ``repro.launch.mesh.resolve_mesh``; "auto" picks
    "distributed" when the ``REPRO_COORD_ADDR`` environment contract is
    set, else a local-device mesh.
    """
    if isinstance(mesh, str):
        from repro.launch.mesh import resolve_mesh
        mesh = resolve_mesh(mesh)
    if _SCENARIO_AXIS not in mesh.axis_names:
        raise ValueError(
            f"engine mesh needs a {_SCENARIO_AXIS!r} axis, got mesh axes "
            f"{mesh.axis_names}")
    return mesh


def pad_scenario_axis(tree, multiple: int):
    """Right-pad the leading (scenario) axis of every leaf to a multiple
    of ``multiple`` by repeating the last scenario.

    Replicated real scenarios keep every padded lane numerically
    well-defined (no zero-hour division edge cases); the caller slices
    the outputs back with :func:`unpad_scenario_axis`.  Returns
    ``(padded_tree, original_n)``.
    """
    leaves = jax.tree.leaves(tree)
    n = int(leaves[0].shape[0])
    pad = (-n) % multiple
    if pad == 0:
        return tree, n
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])]), tree), n


def unpad_scenario_axis(tree, n: int):
    """Slice the leading (scenario) axis of every leaf back to ``n``."""
    return jax.tree.map(lambda x: x[:n], tree)


def _mesh_cache_key(mesh) -> tuple:
    """Identify a mesh by its device topology, not object identity.

    ``Mesh.__eq__``/``__hash__`` are identity-based enough that two
    equivalently-constructed meshes (same devices in the same layout,
    same axis names) used to miss the cache -- recompiling the sweep --
    while a dead Mesh object kept its compiled executable (and the device
    buffers it pins) alive in the cache forever.  Keying on the device
    ids + layout + axis names makes equivalent meshes share one entry.
    """
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), mesh.devices.shape)


# compiled sharded programs, keyed on (kind, static config, mesh topology)
_SHARDED_CACHE: dict = {}


def sharded_cache_size() -> int:
    """Number of compiled sharded programs currently cached (tests pin
    that equivalent meshes do NOT grow this)."""
    return len(_SHARDED_CACHE)


def clear_sharded_cache() -> None:
    _SHARDED_CACHE.clear()


def _sharded_seconds_fn(cfg: EngineConfig, reduce: str, mesh,
                        has_loads: bool, has_ops: bool = False):
    """jit(shard_map(vmap(rollout))) over the scenario axis, cached per
    (static config, mesh topology) so repeated sweeps -- including ones
    that rebuild an equivalent mesh -- reuse the compiled program.

    Every input leaf and every output leaf carries a leading scenario
    axis and the per-scenario rollouts are independent (no collectives),
    so in/out specs are uniformly P("scenario"); each device runs the
    same fused scan over its N/n_dev slice of the batch.

    ``has_loads``/``has_ops`` are part of the key only: a None vs array
    loads/ops arg changes the traced arg pytree.
    """
    key = ("seconds", cfg, reduce, _mesh_cache_key(mesh), has_loads,
           has_ops)
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        spec = P(_SCENARIO_AXIS)

        def run(batch, freq, base_loads, load_keys, scan_keys, ops):
            return _engine_seconds_vmapped(cfg, reduce, batch, freq,
                                           base_loads, load_keys, scan_keys,
                                           ops)

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(spec,) * 6,
            out_specs=spec, check_vma=False))
        _SHARDED_CACHE[key] = fn
    return fn


def _sharded_hourly_fn(cfg: EngineConfig, mesh, has_ops: bool = False):
    key = ("hourly", cfg, _mesh_cache_key(mesh), has_ops)
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        spec = P(_SCENARIO_AXIS)
        fn = jax.jit(jax.shard_map(
            partial(_engine_hourly_vmapped, cfg), mesh=mesh,
            in_specs=(spec, spec), out_specs=spec,
            check_vma=False))
        _SHARDED_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Host-side scenario prep + the public rollout
# ---------------------------------------------------------------------------


@jax.jit
def _scenario_keys_jit(seeds) -> tuple[jax.Array, jax.Array]:
    keys = jax.vmap(jax.random.PRNGKey)(seeds)
    pairs = jax.vmap(partial(jax.random.split, num=2))(keys)
    return pairs[:, 0], pairs[:, 1]


def scenario_keys(batch: ScenarioBatch) -> tuple[jax.Array, jax.Array]:
    """Per-scenario (load_key, scan_key): the same split the twin's
    ``prepare_scenario`` makes from ``PRNGKey(seed)``, as ONE vmapped
    dispatch (bit-exact vs the former per-scenario split loop, which cost
    one device round-trip per scenario)."""
    return _scenario_keys_jit(jnp.asarray(batch.seed))


def base_loads(cfg: EngineConfig, batch: ScenarioBatch) -> jax.Array:
    """(N, T, H) unscaled per-host demand archetypes, materialised.

    The rollout itself no longer needs this buffer -- the scan generates
    each second's row in-scan from the counter-based PRNG (see
    ``twin.host_loads_at``) -- but parity tests, the benchmark baselines
    and measured-data replays still want the explicit (N, T, H) input, so
    it is kept as the reference materialisation of the same trace.
    Scenarios sharing a seed share the trace.
    """
    T = int(batch.h_max) * 3600
    load_keys, _ = scenario_keys(batch)
    cache: dict[int, jax.Array] = {}
    rows = []
    for i, s in enumerate(np.asarray(batch.seed)):
        if int(s) not in cache:
            cache[int(s)] = twin_lib.host_loads_trace(
                cfg.n_hosts, T, load_keys[i])
        rows.append(cache[int(s)])
    return jnp.stack(rows)


def engine_rollout(cfg: EngineConfig, batch: ScenarioBatch, *,
                   reduce: str = "summary", freq=None, loads=None,
                   ops=None, mesh=None) -> dict:
    """Replay a ScenarioBatch through all composed tiers in ONE compiled
    ``jit(vmap(lax.scan))`` call.

    reduce="summary"  only running aggregates cross the scan boundary: every
                      returned leaf is (N,), (N, H_max) or (N, e_max) --
                      device memory does not scale with the horizon T.
    reduce="full"     additionally stacks per-second TwinMetrics plus the
                      (N, T) trigger/shed/load traces (the parity surface).

    ``freq``/``loads`` override the synthesised 1 Hz frequency traces and
    demand archetypes (e.g. to replay measured data); both are validated
    against the batch's (N, T = h_max*3600) shape up front.  By default
    ``freq`` is synthesised from the batch's seeds and the demand rows
    are generated *in-scan* from the counter-based PRNG, so the rollout's
    peak input memory is O(N*H_max) -- no (N, T, H) buffer exists unless
    the caller materialises one.

    ``ops`` replays externally committed hourly trajectories through the
    real settlement instead of the in-graph Tier-3 search: a
    ``(mu_h, rho_h)`` pair of (N, H_max) arrays (the differentiable
    bidder's output, ``repro.optim.bidding``).  ``None`` (the default)
    keeps the pre-override graph bit-identical.

    With ``cfg.telemetry=True`` the output gains a ``"telemetry"`` dict
    (per-hour health moments, day-level histograms, per-event response
    times vs the product's activation budget -- see
    ``repro.obs.telemetry``); leaves stay (N,), (N, H_max), (N, B) or
    (N, e_max), so summary mode keeps its O(N*H + N*B) output bound.

    A proportional batch (FCR-CE) returns the droop's response sums,
    per-block verdicts and block settlement in place of the event buffers
    (:func:`_rollout_droop_one`); :func:`publish_fcr_counters` adds its
    counts to the tracer's metrics once the caller holds the outputs.

    ``mesh`` shards the sweep over devices: pass a Mesh with a
    ``"scenario"`` axis (see ``repro.launch.mesh.resolve_mesh``) or
    ``"auto"`` for a 1-D mesh over every local device.  The batch is
    right-padded to a multiple of the device count by replicating the
    last scenario, each device scans its slice via ``shard_map``, and the
    outputs are sliced back -- same results as the single-device path to
    fp32 reassociation tolerance.  With ``cfg.with_seconds=False`` only
    the hourly tiers run (sharded the same way when ``mesh`` is given).
    """
    if reduce not in ("summary", "full"):
        raise ValueError(f"reduce must be 'summary' or 'full', got {reduce!r}")
    if mesh is not None:
        mesh = _resolve_mesh(mesh)
    if ops is not None:
        mu_ops, rho_ops = ops
        want = (batch.n, int(batch.h_max))
        mu_ops = jnp.asarray(mu_ops, jnp.float32)
        rho_ops = jnp.asarray(rho_ops, jnp.float32)
        if mu_ops.shape != want or rho_ops.shape != want:
            raise ValueError(
                f"ops override must be a (mu_h, rho_h) pair of shape "
                f"(N, H_max) = {want}, got {mu_ops.shape} / "
                f"{rho_ops.shape}")
        ops = (mu_ops, rho_ops)
    if not cfg.with_seconds:
        if mesh is None:
            return _engine_hourly_jit(cfg, batch, ops)
        (padded, ops_p), n = pad_scenario_axis(
            (batch, ops), mesh.shape[_SCENARIO_AXIS])
        fn = _sharded_hourly_fn(cfg, mesh, ops is not None)
        return unpad_scenario_axis(fn(padded, ops_p), n)
    n, T = batch.n, int(batch.h_max) * 3600
    if batch.proportional and cfg.telemetry:
        raise ValueError("telemetry taps the triggered products' events; "
                         "a proportional batch has none")
    if freq is None:
        freq, _ = frequency.synthesize_frequency_batch(
            frequency_seeds(batch), batch.product_idx, n_seconds=T,
            events_per_day=cfg.events_per_day,
            max_events=cfg.max_freq_events, proportional=batch.proportional)
    elif freq.shape != (n, T):
        raise ValueError(
            f"freq override must have shape (N, T) = ({n}, {T}) = "
            f"(batch.n, batch.h_max * 3600), got {freq.shape}")
    if loads is not None and loads.shape != (n, T, cfg.n_hosts):
        raise ValueError(
            f"loads override must have shape (N, T, H) = "
            f"({n}, {T}, {cfg.n_hosts}) = (batch.n, batch.h_max * 3600, "
            f"cfg.n_hosts), got {loads.shape}")
    load_keys, scan_keys = scenario_keys(batch)
    if mesh is None:
        return _engine_seconds_jit(cfg, reduce, batch, freq, loads,
                                   load_keys, scan_keys, ops)
    args, n = pad_scenario_axis(
        (batch, freq, loads, load_keys, scan_keys, ops),
        mesh.shape[_SCENARIO_AXIS])
    fn = _sharded_seconds_fn(cfg, reduce, mesh, loads is not None,
                             ops is not None)
    return unpad_scenario_axis(fn(*args), n)


# ---------------------------------------------------------------------------
# Streaming sweep executor: chunked rollouts + online monoid aggregation
# ---------------------------------------------------------------------------
#
# engine_rollout materialises its whole batch (and its whole output) at
# once, which caps a sweep at what one host holds.  The streaming path
# reduces each chunk's reduce="summary" output into a flat dict of
# commutative-monoid accumulators (chunk_summary), folds chunks together
# with summary_merge (suffix convention: keys ending "_max"/"_min" merge
# by max/min, everything else by +), and converts the terminal aggregate
# into fleet-level metrics host-side (sweep_finalize).  Because the
# merge is commutative and associative, ANY chunking/ordering -- and any
# split across devices (per-device aggregate lanes) or processes
# (process_slice + out-of-band merge) -- reproduces the monolithic
# numbers to fp32 reassociation tolerance.

# extensive (pure-sum) aggregate keys shared by summary_init/chunk_summary
_SWEEP_SCHED_SUMS = ("sched_it_mwh", "sched_fac_mwh", "sched_co2_t",
                     "sched_co2_it_t", "sched_cfe_fac_mwh",
                     "sched_tokens_mtok")
_SWEEP_SECONDS_SUMS = ("it_mwh", "fac_mwh", "shed_it_mwh", "active_s",
                       "capacity_eur", "penalty_eur", "net_eur",
                       "n_events", "n_compliant", "tokens_mtok",
                       "tokens_ckpt_mtok", "tokens_lost_mtok")


# the same for a proportional product's seconds tier
_SWEEP_DROOP_SUMS = ("it_mwh", "fac_mwh", "shed_it_mwh", "active_s", "up_s",
                     "req_dn_mwh", "req_up_mwh", "dlv_dn_mwh", "dlv_up_mwh",
                     "n_blocks", "n_blocks_failed", "capacity_eur",
                     "penalty_eur", "net_eur", "tokens_mtok",
                     "tokens_lost_mtok")
_SWEEP_TWIN_SUMS = ("seconds", "warm_s", "ar4_err_s", "track_err_s",
                    "chip_mean_s", "chip_p95_s", "thr_s",
                    "committed_mw_hours")


def summary_init(cfg: EngineConfig, proportional: bool = False) -> dict:
    """The monoid identity: the aggregate of zero scenarios (of a
    triggered or a ``proportional`` product).

    Every leaf is float32 (counts included) so the donated aggregate
    buffer keeps one dtype across merges; extremes start at -/+inf and
    :func:`sweep_finalize` maps never-observed extremes back to 0.
    """
    z = jnp.float32(0.0)
    neg, pos = jnp.float32(-jnp.inf), jnp.float32(jnp.inf)
    s = {k: z for k in ("n_scenarios", "hours", "mu_hours", "rho_hours",
                        "cfe_mu_hours") + _SWEEP_SCHED_SUMS}
    if not cfg.with_seconds:
        return s
    if proportional:
        s.update({k: z for k in _SWEEP_TWIN_SUMS + _SWEEP_DROOP_SUMS})
        return s
    s.update({k: z for k in _SWEEP_TWIN_SUMS
              + ("n_compliant_sched", "ev_delivered_frac_sum",
                 "ev_t_full_ms_sum", "ev_budget_ok", "ev_sustain_ok",
                 "ev_delivered_ok")
              + _SWEEP_SECONDS_SUMS})
    s["ev_t_full_ms_max"] = neg
    if cfg.telemetry:
        s.update(
            tel_track_hist=jnp.zeros(obs_tel.N_TRACK_BUCKETS, jnp.float32),
            tel_resp_hist=jnp.zeros(obs_tel.N_RESP_BUCKETS, jnp.float32),
            tel_rls2=z, tel_track2=z, tel_sat_s=z, tel_n_budget_ok=z,
            tel_resp_ms_sum=z, tel_resp_n=z,
            tel_resp_ms_max=neg, tel_slew_max=neg, tel_slew_min=pos)
    return s


def chunk_summary(cfg: EngineConfig, out: dict, batch: ScenarioBatch,
                  lane=None) -> dict:
    """Reduce one chunk's ``reduce="summary"`` rollout output into the
    streaming aggregate dict (same keys as :func:`summary_init`).

    Pure jnp on (N,)-leading leaves, so it runs inside the jitted sweep
    step (and inside ``shard_map``, where N is the per-device slice).
    ``lane`` is the (N,) validity mask: 0.0 marks lanes added by
    ``pad_scenario_axis``, whose replicate-last-scenario padding is
    numerically well-defined but must NOT leak into fleet sums -- an
    unmasked merge double-counts the final real scenario.  Default: all
    lanes valid (the monolithic-output case).

    Intensive metrics are re-extensified with the same data-independent
    weights the rollout normalised by (per-scenario valid seconds
    ``hours*3600``, warm seconds ``hours*3600 - warmup_s``, valid hours),
    so the monolithic normalisation inverts exactly and per-chunk merges
    reproduce the monolithic summary to fp32 reassociation tolerance.
    """
    lane = (jnp.ones((batch.n,), jnp.float32) if lane is None
            else jnp.asarray(lane, jnp.float32))
    hours = jnp.asarray(batch.hours, jnp.float32)
    hv = jnp.maximum(hours, 1.0)              # _hourly_one's hour count
    s = dict(
        n_scenarios=jnp.sum(lane),
        hours=jnp.sum(lane * hours),
        mu_hours=jnp.sum(lane * out["mean_mu"] * hv),
        rho_hours=jnp.sum(lane * out["mean_rho"] * hv),
        cfe_mu_hours=jnp.sum(lane * out["cfe_mu"]),
    )
    for k in _SWEEP_SCHED_SUMS:
        s[k] = jnp.sum(lane * out[k])
    if "it_mwh" not in out:                   # hourly-only rollout
        return s
    n_s = hours * 3600.0                      # per-scenario valid seconds
    nc = jnp.maximum(n_s, 1.0)
    nw = jnp.maximum(n_s - cfg.warmup_s, 1.0)  # seconds past RLS warm-up
    s.update(
        seconds=jnp.sum(lane * n_s),
        warm_s=jnp.sum(lane * jnp.maximum(n_s - cfg.warmup_s, 0.0)),
        ar4_err_s=jnp.sum(lane * out["ar4_mae_norm"] * nw),
        track_err_s=jnp.sum(lane * out["tracking_err_mean"] * nw),
        chip_mean_s=jnp.sum(lane * out["chip_power_mean"] * nc),
        chip_p95_s=jnp.sum(lane * out["chip_power_p95"] * nc),
        thr_s=jnp.sum(lane * out["thr_mean"] * nc),
        committed_mw_hours=jnp.sum(lane * out["committed_mw"] * hv),
    )
    if batch.proportional:
        for k in _SWEEP_DROOP_SUMS:
            s[k] = jnp.sum(lane * out[k].astype(jnp.float32))
        return s
    for k in _SWEEP_SECONDS_SUMS:
        s[k] = jnp.sum(lane * out[k].astype(jnp.float32))
    ev = out["events"]
    evs = out["events_sched"]
    vm = lane[:, None] * ev.valid.astype(jnp.float32)
    s.update(
        n_compliant_sched=jnp.sum(
            lane[:, None] * (evs.valid & evs.compliant)),
        ev_delivered_frac_sum=jnp.sum(vm * ev.delivered_frac),
        ev_t_full_ms_sum=jnp.sum(vm * ev.t_full_ms),
        ev_t_full_ms_max=jnp.max(
            jnp.where(vm > 0, ev.t_full_ms, -jnp.inf)),
        ev_budget_ok=jnp.sum(vm * ev.budget_ok),
        ev_sustain_ok=jnp.sum(vm * ev.sustain_ok),
        ev_delivered_ok=jnp.sum(vm * ev.delivered_ok),
    )
    if cfg.telemetry and "telemetry" in out:
        s.update(obs_tel.sweep_summary(out["telemetry"], lane,
                                       warmup_s=cfg.warmup_s))
    return s


def summary_merge(agg: dict, chunk: dict) -> dict:
    """Fold one chunk aggregate into the running aggregate.

    Commutative and associative by construction -- keys ending ``_max``
    merge by maximum, ``_min`` by minimum, everything else by addition --
    so chunking, chunk order, device lanes and process splits all
    reassociate freely (fp32 sum reassociation is the only tolerance).
    Pure (works on jnp tracers inside the jitted sweep step and on host
    numpy when merging per-process aggregates out-of-band).
    """
    if agg.keys() != chunk.keys():
        raise ValueError(
            f"aggregate key mismatch: {sorted(agg)} vs {sorted(chunk)} "
            "(merging summaries from different EngineConfig modes?)")
    out = {}
    for k, a in agg.items():
        b = chunk[k]
        if k.endswith("_max"):
            out[k] = jnp.maximum(a, b)
        elif k.endswith("_min"):
            out[k] = jnp.minimum(a, b)
        else:
            out[k] = a + b
    return out


def _finite(x) -> float:
    x = float(x)
    return x if np.isfinite(x) else 0.0


def sweep_finalize(agg: dict) -> dict:
    """Terminal aggregate -> fleet-level metrics (host-side numpy).

    Means are recovered from the carried (numerator, weight) pairs;
    never-observed extremes (still at -/+inf from :func:`summary_init`)
    report as 0.  Keys reuse the per-scenario summary names where the
    fleet metric is the scenario-weighted mean of that quantity.
    """
    a = {k: np.asarray(v) for k, v in agg.items()}
    hours = float(a["hours"])
    hv = max(hours, 1.0)
    out = dict(
        n_scenarios=float(a["n_scenarios"]),
        hours=hours,
        scenario_days=hours / 24.0,
        mean_mu=float(a["mu_hours"]) / hv,
        mean_rho=float(a["rho_hours"]) / hv,
        cfe_mu=float(a["cfe_mu_hours"]) / hv,
    )
    for k in _SWEEP_SCHED_SUMS:
        out[k] = float(a[k])
    if "seconds" not in a:
        return out
    sec = max(float(a["seconds"]), 1.0)
    warm = max(float(a["warm_s"]), 1.0)
    out.update(
        seconds=float(a["seconds"]),
        ar4_mae_norm=float(a["ar4_err_s"]) / warm,
        tracking_err_mean=float(a["track_err_s"]) / warm,
        chip_power_mean=float(a["chip_mean_s"]) / sec,
        chip_power_p95=float(a["chip_p95_s"]) / sec,
        thr_mean=float(a["thr_s"]) / sec,
        committed_mw=float(a["committed_mw_hours"]) / hv)
    if "n_blocks" in a:                       # a proportional product
        out.update({k: float(a[k]) for k in _SWEEP_DROOP_SUMS})
        out["block_compliance"] = 1.0 - out["n_blocks_failed"] / max(
            out["n_blocks"], 1.0)
        return out
    n_ev = max(float(a["n_events"]), 1.0)
    out.update(
        compliance=float(a["n_compliant"]) / n_ev,
        compliance_sched=float(a["n_compliant_sched"]) / n_ev,
        delivered_frac_mean=float(a["ev_delivered_frac_sum"]) / n_ev,
        resp_ms_mean=float(a["ev_t_full_ms_sum"]) / n_ev,
        resp_ms_max=_finite(a["ev_t_full_ms_max"]),
        budget_ok_frac=float(a["ev_budget_ok"]) / n_ev,
        sustain_ok_frac=float(a["ev_sustain_ok"]) / n_ev,
        delivered_ok_frac=float(a["ev_delivered_ok"]) / n_ev,
    )
    for k in _SWEEP_SECONDS_SUMS:
        out[k] = float(a[k])
    if "tel_rls2" in a:
        out["telemetry"] = dict(
            track_hist=np.asarray(a["tel_track_hist"], np.float64),
            resp_hist=np.asarray(a["tel_resp_hist"], np.float64),
            rls_rms=float(np.sqrt(float(a["tel_rls2"]) / warm)),
            track_rms=float(np.sqrt(float(a["tel_track2"]) / warm)),
            sat_frac=float(a["tel_sat_s"]) / sec,
            n_budget_ok=float(a["tel_n_budget_ok"]),
            resp_ms_mean=(float(a["tel_resp_ms_sum"])
                          / max(float(a["tel_resp_n"]), 1.0)),
            resp_ms_max=_finite(a["tel_resp_ms_max"]),
            slew_max=_finite(a["tel_slew_max"]),
            slew_min=_finite(a["tel_slew_min"]),
        )
    return out


def _sweep_body(cfg: EngineConfig, batch: ScenarioBatch, lane) -> dict:
    """One chunk, traced: synthesise the chunk's frequency traces and
    scenario keys IN-GRAPH (host never materialises them), run the fused
    vmapped rollout, reduce to the aggregate dict.  Demand rows are
    already generated in-scan from the counter-based PRNG, so peak input
    memory is O(chunk * H_max)."""
    if not cfg.with_seconds:
        return chunk_summary(cfg, _engine_hourly_vmapped(cfg, batch),
                             batch, lane)
    T = int(batch.h_max) * 3600
    freq, _ = frequency.synthesize_frequency_batch(
        frequency_seeds(batch), batch.product_idx, n_seconds=T,
        events_per_day=cfg.events_per_day, max_events=cfg.max_freq_events,
        proportional=batch.proportional)
    load_keys, scan_keys = _scenario_keys_jit(jnp.asarray(batch.seed))
    out = _engine_seconds_vmapped(cfg, "summary", batch, freq, None,
                                  load_keys, scan_keys)
    return chunk_summary(cfg, out, batch, lane)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _sweep_step_jit(cfg: EngineConfig, agg: dict, batch: ScenarioBatch,
                    lane) -> dict:
    """One streamed chunk folded into the donated aggregate: the
    aggregate buffers are reused in place, so sweep memory is O(chunk)
    regardless of how many chunks stream through."""
    return summary_merge(agg, _sweep_body(cfg, batch, lane))


def _sweep_step_sharded(cfg: EngineConfig, mesh):
    """Sharded sweep step: per-DEVICE aggregate lanes, no collectives.

    The aggregate carries a leading ``n_dev`` axis sharded over the
    scenario mesh axis; inside ``shard_map`` each device strips its
    (1, ...) block, folds its slice of the chunk into it, and restores
    the lane axis.  Cross-device combination happens once, host-side, at
    the end of the sweep (``summary_merge`` over the lanes) -- the
    steady-state step stays collective-free.
    """
    key = ("sweep", cfg, _mesh_cache_key(mesh))
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        spec = P(_SCENARIO_AXIS)

        def run(agg, batch, lane):
            local = jax.tree.map(lambda x: x[0], agg)
            merged = summary_merge(local, _sweep_body(cfg, batch, lane))
            return jax.tree.map(lambda x: x[None], merged)

        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False), donate_argnums=(0,))
        _SHARDED_CACHE[key] = fn
    return fn


def _pad_chunk(batch: ScenarioBatch, pad_to: int):
    """Pad a chunk to the fixed lane count (one compiled program for
    every chunk, including the final partial one) and return the lane
    validity mask that keeps the replicated padding out of the sums."""
    n = batch.n
    if n > pad_to:
        raise ValueError(f"chunk of {n} scenarios exceeds lane count "
                         f"{pad_to}")
    lane = (jnp.arange(pad_to) < n).astype(jnp.float32)
    if n == pad_to:
        return batch, lane
    padded, _ = pad_scenario_axis(batch, pad_to)
    return padded, lane


def engine_sweep(cfg: EngineConfig, specs, *, chunk_size: int, mesh=None,
                 h_max: int | None = None, finalize: bool = True,
                 progress=None) -> dict:
    """Stream an arbitrarily large scenario sweep through chunk-shaped
    rollouts with online aggregation: memory is O(chunk_size), not
    O(len(specs)).

    ``specs`` is any random-access sequence of ScenarioSpec; each chunk's
    traces are synthesised only when its chunk is built
    (``scenario_chunk``), every chunk is padded to one fixed lane count
    (``chunk_size`` rounded up to the mesh's device count) so the whole
    sweep is ONE compiled program, and each step folds its chunk into
    donated aggregate buffers via the :func:`summary_merge` monoid.

    ``mesh`` shards each chunk over a ``"scenario"`` mesh axis ("auto" /
    "local" / "distributed" resolve through ``launch.mesh.resolve_mesh``)
    with per-device aggregate lanes, combined host-side once at the end.
    In a multi-process launch (the ``REPRO_COORD_ADDR`` env contract)
    every process calls this with the SAME ``specs`` and sweeps only its
    ``process_slice`` of the index range -- no host ever materialises
    the global batch; with ``finalize=False`` the raw per-process
    aggregate comes back for out-of-band merging.

    ``h_max`` pins the padded hour axis (default: the global longest
    horizon -- computed from specs without building any batch).
    ``progress(chunks_done, n_chunks)`` is called after each folded
    chunk.  Returns :func:`sweep_finalize` metrics, or the raw aggregate
    dict when ``finalize=False``.  The specs sell one kind of product
    (``scenarios.product_kind``); a proportional sweep publishes its FCR
    counts (:func:`publish_fcr_counters`) from the aggregate it reads
    back at the end.
    """
    from repro.launch import mesh as mesh_lib
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    if len(specs) == 0:
        raise ValueError("empty scenario list")
    mesh_lib.ensure_distributed()
    n_dev = None
    if mesh is not None:
        mesh = _resolve_mesh(mesh)
        n_dev = mesh.shape[_SCENARIO_AXIS]
    if h_max is None:
        h_max = max(s.horizon_h for s in specs)
    proportional = product_kind(specs)
    if proportional and cfg.telemetry:
        raise ValueError("telemetry taps the triggered products' events; "
                         "a proportional sweep has none")
    lo0, hi0 = mesh_lib.process_slice(len(specs))
    pad_to = (chunk_size if n_dev is None
              else -(-chunk_size // n_dev) * n_dev)
    agg = summary_init(cfg, proportional)
    if mesh is None:
        # .copy() forces one distinct device buffer per leaf: jax caches
        # equal scalar constants, and donating an aliased buffer twice in
        # one step is an error
        agg = jax.tree.map(lambda x: jnp.asarray(x).copy(), agg)
    else:
        # materialised per-device lanes (donation needs real buffers), laid
        # out over the mesh as the step returns them, so the first chunk
        # runs the same compiled program as every later one
        lanes = NamedSharding(mesh, P(_SCENARIO_AXIS))
        agg = jax.tree.map(lambda x: jax.device_put(
            np.tile(np.asarray(x)[None], (n_dev,) + (1,) * np.ndim(x)),
            lanes), agg)
        step = _sweep_step_sharded(cfg, mesh)
    starts = range(lo0, hi0, chunk_size)
    for i, lo in enumerate(starts):
        batch, lane = _pad_chunk(
            scenario_chunk(specs, lo, min(lo + chunk_size, hi0),
                           h_max=h_max), pad_to)
        if mesh is None:
            agg = _sweep_step_jit(cfg, agg, batch, lane)
        else:
            agg = step(agg, batch, lane)
        if progress is not None:
            progress(i + 1, len(starts))
    host = jax.tree.map(np.asarray, agg)
    if mesh is not None:
        merged = jax.tree.map(lambda x: x[0], host)
        for d in range(1, n_dev):
            merged = summary_merge(
                merged, jax.tree.map(lambda x, d=d: x[d], host))
        host = jax.tree.map(np.asarray, merged)
    if proportional and cfg.with_seconds:
        publish_fcr_counters(host)
    return sweep_finalize(host) if finalize else host


def summarize_rollout(cfg: EngineConfig, batch: ScenarioBatch,
                      full: dict) -> dict:
    """Recompute the streaming summary from a reduce="full" rollout.

    The parity oracle for the in-scan reducer: applying this to the full
    per-second stacks must reproduce engine_rollout(reduce="summary")'s
    aggregates (same gating, same normalisation).
    """
    m: twin_lib.TwinMetrics = full["metrics"]
    T = m.it_power.shape[-1]
    t = np.arange(T)
    hours = np.asarray(batch.hours)
    mw = np.asarray(batch.mw)
    design_host = cfg.chips_per_host * cfg.chip_tdp
    out = {}
    g = (t[None, :] < hours[:, None] * 3600)
    w = g & (t[None, :] >= cfg.warmup_s)
    nw = np.maximum(w.sum(-1), 1)
    n = np.maximum(g.sum(-1), 1)
    err = np.asarray(m.ar4_abs_err).mean(-1) / design_host     # (N, T)
    out["ar4_mae_norm"] = (err * w).sum(-1) / nw
    out["tracking_err_mean"] = (np.asarray(m.tracking_err) * w).sum(-1) / nw
    out["chip_power_mean"] = (np.asarray(m.chip_power_mean) * g).sum(-1) / n
    out["chip_power_p95"] = (np.asarray(m.chip_power_p95) * g).sum(-1) / n
    L = np.asarray(m.it_power) / cfg.design_it_w
    F = np.asarray(m.facility_power) / cfg.design_it_w
    out["it_mwh"] = (L * g).sum(-1) * mw / 3600.0
    out["fac_mwh"] = (F * g).sum(-1) * mw / 3600.0
    out["active_s"] = (np.asarray(full["shed"]) & g).sum(-1)
    # workload throughput: the same shared curve, reduced from the stacks
    clock_w = np.asarray(workload_lib.CLOCK_W)[np.asarray(batch.mix_idx)]
    thr = np.asarray(workload_lib.throughput_frac(clock_w[:, None],
                                                  L.astype(np.float32)))
    thr_sum = (thr * g).sum(-1)
    out["thr_mean"] = thr_sum / n
    tok_rate = np.asarray(workload_lib.TOKENS_PER_MW_S)[
        np.asarray(batch.mix_idx)]
    out["tokens_mtok"] = thr_sum * mw * tok_rate / 1e6
    return out
