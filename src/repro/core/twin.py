"""Cluster digital twin: the multiscale 24 h simulation behind paper Fig. 4.

Composes all three tiers over a simulated fleet at 1 Hz (Tier-2 cadence):

  Tier-3 (hourly)  operating point (mu, rho) from the CI/T_amb forecast,
  Tier-2 (1 Hz)    per-host AR(4)/RLS prediction + cap rebalancing,
  Tier-1 (200 Hz)  represented quasi-statically at the 1 Hz tick (the PID
                   settles in <30 ms << 1 s; its transient behaviour is
                   exercised separately by E2/E4/E7 at full rate),
  FFR events       instant envelope shed to (mu - rho) via the island path,
  FCR-CE droop     envelope mu - rho * a(t) every second, both directions
                   (:func:`droop_tick`).

Everything is one `jax.lax.scan` over seconds with vector state across
hosts*chips, which is how the twin reaches the paper's >26 000x real-time
(86 400 simulated seconds in a few wall-clock seconds, jitted).

The scan body is pure over a :class:`TwinInputs` bundle of per-second
traces, so a batch of scenarios (grids x seeds x seasons) replays as ONE
jitted ``vmap(scan)`` call: prepare each scenario host-side with
:func:`prepare_scenario`, stack with :func:`stack_scenarios`, and run
:func:`run_twin_batch`.  `run_twin` is the single-scenario wrapper over the
same code path.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.ar4 as ar4_lib
import repro.core.plant as plant_lib
import repro.core.pue as pue_lib
import repro.core.tier3 as tier3_lib
import repro.grid.markets as markets
import repro.grid.signals as signals
import repro.workload.model as workload_lib


class TwinMetrics(NamedTuple):
    host_power: jax.Array       # (T, H) W
    host_pred: jax.Array        # (T, H) W  Tier-2 one-step-ahead
    ar4_abs_err: jax.Array      # (T, H) W  a-priori |err|
    chip_power_mean: jax.Array  # (T,)
    chip_power_p95: jax.Array   # (T,)
    envelope: jax.Array         # (T,) W cluster envelope setpoint
    it_power: jax.Array         # (T,) W cluster IT power
    facility_power: jax.Array   # (T,) W at the meter
    ffr_active: jax.Array       # (T,) bool
    tracking_err: jax.Array     # (T,) |it - envelope| / envelope


@dataclass(frozen=True)
class TwinConfig:
    n_hosts: int = 100
    chips_per_host: int = 3
    chip_tdp: float = plant_lib.TDP
    pue_design: float = pue_lib.PUE_DESIGN
    pue_aware: bool = True
    seconds: int = 86_400
    seed: int = 0
    # step-synchronous training transient (repro.workload.step_transient):
    # amplitude 0 (the default) leaves the demand traces exactly as before
    step_transient_amp: float = 0.0
    step_period_s: float = workload_lib.STEP_PERIOD_S_DEFAULT

    @property
    def n_chips(self) -> int:
        return self.n_hosts * self.chips_per_host

    @property
    def design_it_w(self) -> float:
        return self.n_chips * self.chip_tdp


class HostLoadParams(NamedTuple):
    """O(H) per-scenario constants of the counter-based 1 Hz load synthesis.

    Everything :func:`host_loads_block` needs to produce the demand rows
    of ANY hour block from the scenario's load key alone -- archetype
    stats, per-host slow-wave/jitter phases, and the white-noise key that
    is ``fold_in``-ed with the block index.  Replaces the materialised
    (T, H) trace as the engine's load input: O(H) instead of O(T*H).
    """

    mean: jax.Array        # (H,) archetype mean utilisation
    fast_sigma: jax.Array  # (H,) white-noise sigma
    slow_sigma: jax.Array  # (H,) band-limited wander sigma
    phases: jax.Array      # (H, 4) slow-wave phase offsets
    is_bursty: jax.Array   # (H,) bool: duty-cycled archetype
    duty_phase: jax.Array  # (H,) bursty duty-cycle phase offset
    jitter_ph: jax.Array   # (H,) bursty edge-jitter phase
    fast_key: jax.Array    # PRNG key; fold_in(block) -> the block's noise


_SLOW_FREQS_HZ = jnp.asarray(plant_lib.SLOW_FREQS_HZ)

# Counter-based synthesis granularity: the PRNG counter is the hour-sized
# block index, so one fold_in + one normal((3600, H)) draw serves 3600
# ticks.  Per-*second* counters measure ~30 % overhead on the fused
# engine tick (2 threefry dispatches + erfinv per tick inside the scan
# body); per-hour blocks amortise them into one vectorised draw that the
# engine's outer (hourly) scan level generates, keeping live input
# memory O(BLOCK * H) per scenario -- constant in the horizon T.
LOAD_BLOCK_S = 3600


def _host_kinds(n_hosts: int) -> np.ndarray:
    """Archetype mix across hosts: 50 % matmul-like (training), 30 %
    inference, 20 % bursty."""
    return np.array([0] * (n_hosts // 2)
                    + [1] * (3 * n_hosts // 10)
                    + [2] * (n_hosts - n_hosts // 2 - 3 * n_hosts // 10))


def host_load_params(n_hosts: int, key) -> HostLoadParams:
    """Scenario load key -> the O(H) constants of the per-second synthesis."""
    kinds = _host_kinds(n_hosts)
    stats = np.array([[plant_lib._ARCHETYPES[w][f] for w in
                       ("matmul", "inference", "bursty")]
                      for f in ("mean", "fast_sigma", "slow_sigma")],
                     np.float32)[:, kinds]                      # (3, H)
    k_fast, k_ph, k_jit = jax.random.split(key, 3)
    return HostLoadParams(
        mean=jnp.asarray(stats[0]),
        fast_sigma=jnp.asarray(stats[1]),
        slow_sigma=jnp.asarray(stats[2]),
        phases=jax.random.uniform(k_ph, (n_hosts, 4), minval=0.0,
                                  maxval=2 * jnp.pi),
        is_bursty=jnp.asarray(kinds == 2),
        duty_phase=jnp.asarray(kinds * 0.37, jnp.float32),
        jitter_ph=jax.random.uniform(k_jit, (n_hosts,), maxval=6.28),
        fast_key=k_fast,
    )


def host_loads_rows(p: HostLoadParams, tf, fast) -> jax.Array:
    """(K,) absolute seconds + (K, H) white noise -> (K, H) demand rows.

    The deterministic body of the counter-based synthesis, factored out of
    :func:`host_loads_block` so callers that draw their white noise on a
    different counter granularity -- the online service's live per-tick
    row (``repro.service.state``, one ``fold_in`` per second instead of
    per hour block) -- run the IDENTICAL slow-wave/bursty demand model.
    """
    # sin(w t + ph) expanded by angle addition: the trig-of-time factors
    # depend only on the block index, so under the engine's vmap over
    # scenarios they are computed ONCE for the whole batch (the libm sin
    # calls are what dominates the synthesis otherwise); each scenario
    # pays only the tiny per-host phase contraction.
    ang = 2 * jnp.pi * _SLOW_FREQS_HZ * tf[:, None]             # (K, 4)
    s_t, c_t = jnp.sin(ang), jnp.cos(ang)
    slow = (s_t @ jnp.cos(p.phases).T + c_t @ jnp.sin(p.phases).T) / 2.0
    base = p.mean + p.slow_sigma * slow + p.fast_sigma * fast   # (K, H)
    ang_j = 2 * jnp.pi * plant_lib.BURSTY_JITTER_FREQ_HZ * tf   # (K,)
    jit_t = plant_lib.BURSTY_EDGE_JITTER_S * (
        jnp.sin(ang_j)[:, None] * jnp.cos(p.jitter_ph)[None]
        + jnp.cos(ang_j)[:, None] * jnp.sin(p.jitter_ph)[None])
    frac = jnp.mod((tf[:, None] + jit_t) / plant_lib.BURSTY_PERIOD_S
                   + p.duty_phase, 1.0)
    on = frac < plant_lib.BURSTY_DUTY
    bursty = jnp.where(on, base, plant_lib.BURSTY_LOW + 0.01 * fast)
    return jnp.clip(jnp.where(p.is_bursty, bursty, base), 0.0, 1.0)


def host_loads_block(p: HostLoadParams, b) -> jax.Array:
    """The (LOAD_BLOCK_S, H) demand rows of hour-block ``b``, from the
    counter-based PRNG.

    Pure function of (params, block index): ``fold_in(fast_key, b)``
    seeds the block's white noise and everything else is a vectorised
    function of the absolute second, so a scan level that walks hours can
    synthesise its own demand input instead of gathering from a
    materialised (T, H) buffer.  The trace builder
    :func:`host_loads_trace` is the vmap of this function over blocks --
    identical PRNG bits by construction, float path within 1 ulp (XLA
    reassociates the slow-wave sum differently under vmap).
    """
    t0 = jnp.asarray(b, jnp.int32) * LOAD_BLOCK_S
    tf = (jnp.asarray(t0, jnp.float32)
          + jnp.arange(LOAD_BLOCK_S, dtype=jnp.float32))        # (K,)
    fast = jax.random.normal(jax.random.fold_in(p.fast_key, b),
                             (LOAD_BLOCK_S,) + p.mean.shape)    # (K, H)
    return host_loads_rows(p, tf, fast)


def host_loads_at(p: HostLoadParams, t) -> jax.Array:
    """The (H,) demand row of second ``t``: random access into the
    counter-based synthesis (computes ``t``'s block, takes one row)."""
    b = jnp.asarray(t, jnp.int32) // LOAD_BLOCK_S
    return host_loads_block(p, b)[jnp.asarray(t, jnp.int32) % LOAD_BLOCK_S]


@partial(jax.jit, static_argnames=("n_hosts", "n_seconds"))
def host_loads_trace(n_hosts: int, n_seconds: int, key) -> jax.Array:
    """Materialised (T, H) trace: vmap of :func:`host_loads_block`."""
    p = host_load_params(n_hosts, key)
    nb = -(-n_seconds // LOAD_BLOCK_S)
    blocks = jax.vmap(partial(host_loads_block, p))(
        jnp.arange(nb, dtype=jnp.int32))
    return blocks.reshape(nb * LOAD_BLOCK_S, -1)[:n_seconds]


def _host_loads(cfg: TwinConfig, key) -> jax.Array:
    """Per-host mean-utilisation demand profile at 1 Hz, (T, H)."""
    return host_loads_trace(cfg.n_hosts, cfg.seconds, key)


class TwinInputs(NamedTuple):
    """Per-second traced inputs of one scenario (all precomputed host-side).

    Every leaf is an array, so a list of these stacks into a leading
    scenario axis with `stack_scenarios` and maps through `jax.vmap`.
    """

    loads: jax.Array     # (T, H) per-host demand profile
    mu_sec: jax.Array    # (T,) Tier-3 operating fraction
    rho_sec: jax.Array   # (T,) committed FFR band
    ffr_sec: jax.Array   # (T,) bool FFR activation flag
    t_amb_sec: jax.Array  # (T,) ambient degC
    key: jax.Array       # PRNG key for plant noise


@dataclasses.dataclass(frozen=True)
class TwinScenario:
    """One prepared scenario: scan inputs + the host-side context the
    summary needs (FFR event list, hourly operating points, grid)."""

    inputs: TwinInputs
    grid: signals.GridSignals
    events: list
    mu_h: np.ndarray
    rho_h: np.ndarray
    seed: int


def twin_carry_init(n_hosts: int, chips_per_host: int, key):
    """Initial Tier-2 + plant carry of the 1 Hz scan: (rls, chip_power,
    caps, key).  Shared with the unified ``repro.core.engine`` scan."""
    rls0 = ar4_lib.init_rls(n_hosts)
    chip_power0 = jnp.full((n_hosts, chips_per_host), plant_lib.P_IDLE,
                           jnp.float32)
    caps0 = jnp.full((n_hosts, chips_per_host), plant_lib.CAP_MAX,
                     jnp.float32)
    return (rls0, chip_power0, caps0, key)


def twin_tick(n_hosts: int, chips_per_host: int, chip_tdp: float,
              pue_design, carry, load_h, mu, rho, ffr, t_amb):
    """The 1 Hz fused Tier-2/Tier-1/plant update for one second.

    Factored out of the twin scan so the unified engine runs the IDENTICAL
    physics with the reserve detection fused into the same pass.
    ``pue_design`` may be traced (the engine threads the per-scenario
    design axis through it); the dims are static Python ints/floats.
    Returns (carry, TwinMetrics row).
    """
    H, C = n_hosts, chips_per_host
    design_host = C * chip_tdp
    design_it_w = H * design_host
    rls, chip_power, caps, kk = carry
    kk, k1 = jax.random.split(kk)

    # --- cluster envelope from Tier-3 (+ island shed during FFR) ------
    frac = jnp.where(ffr, mu - rho, mu)
    envelope = frac * design_it_w
    host_env = jnp.full((H,), 1.0) * (frac * design_host)
    # FFR actuation is caps + duty shed: the reserve band is held as
    # instantly-sheddable duty-cycled steps (DESIGN.md §2), so demand
    # itself drops during an activation, not just the cap.
    load_h = load_h * jnp.where(ffr, frac / jnp.maximum(mu, 1e-3), 1.0)

    # --- Tier-2: predict next-second host power, rebalance caps -------
    # RLS runs on normalised host power (see ar4.rls_update numerics).
    pred = ar4_lib.predict(rls) * design_host  # (H,) W
    caps = ar4_lib.host_rebalance(
        pred, host_env, jnp.maximum(chip_power, plant_lib.P_IDLE),
        plant_lib.CAP_MIN, plant_lib.CAP_MAX,
    )

    # --- Tier-1 + plant, quasi-static over the 1 s tick ---------------
    demand = plant_lib.power_model(
        plant_lib.F_NOMINAL, load_h[:, None]
    ) + 2.0 * jax.random.normal(k1, (H, C))
    target = jnp.minimum(demand, caps)
    # FFR deep shed: preemption can idle chips below the 100 W cap
    # floor, down to P_idle + min clocks (~53 W) -- the duty-cycled
    # reserve is job shedding, not just capping (DESIGN.md §2).
    idle_floor = 53.0
    shed_target = jnp.clip(frac * chip_tdp, idle_floor, caps)
    target = jnp.where(ffr, jnp.minimum(target, shed_target), target)
    # 1 s >> tau and >> the ~100 ms governor ramp: quasi-static
    rls, out = _settle(rls, target, pred, envelope, ffr, design_host,
                       design_it_w, pue_design, t_amb)
    return (rls, target, caps, kk), out


def top2(x):
    """The largest and second largest of all of ``x``'s values, ties kept
    and NaN propagated, by one running max/min fold (no sort)."""
    xs = x.reshape(-1)
    t1, t2 = xs[0], jnp.full((), -jnp.inf, xs.dtype)
    for i in range(1, xs.shape[0]):
        t2 = jnp.maximum(t2, jnp.minimum(t1, xs[i]))
        t1 = jnp.maximum(t1, xs[i])
    return t1, t2


def p95(x):
    """``jnp.percentile(x, 95.0)`` over all of float32 ``x``.  The two
    ranks it interpolates are fixed by the static size n: where both lie
    among the two largest values (n <= 21), they come from :func:`top2`,
    which fuses with the tick's elementwise work where a sort does not;
    otherwise the sort.  Ranks and weights are ``jnp.percentile``'s own
    ('linear' in float32), so the order statistics are exact and the
    value agrees to rounding."""
    n = x.size
    q = np.float32(0.95) * np.float32(n - 1)
    low, high = int(np.floor(q)), int(np.ceil(q))
    if n - 1 - low > 1:
        return jnp.percentile(x, 95.0)
    high_w = np.float32(q - np.float32(low))
    low_w = np.float32(1.0) - high_w
    t1, t2 = top2(x)
    low_v = t1 if low == n - 1 else t2
    high_v = t1 if high == n - 1 else t2
    return low_v * low_w + high_v * high_w


def _settle(rls, chip_power, pred, envelope, active, design_host,
            design_it_w, pue_design, t_amb):
    """The tick's plant settles at ``chip_power``: Tier-2's RLS update on
    the new host power, the meter, and the metrics row."""
    host_power = jnp.sum(chip_power, axis=1)  # (H,)
    rls, abs_err_norm = ar4_lib.rls_update(rls, host_power / design_host)
    abs_err = abs_err_norm * design_host

    it = jnp.sum(host_power)
    L = it / design_it_w
    fac = it * pue_lib.pue(L, t_amb, pue_design=pue_design)
    track = jnp.abs(it - envelope) / jnp.maximum(envelope, 1.0)

    out = TwinMetrics(
        host_power=host_power,
        host_pred=pred,
        ar4_abs_err=abs_err,
        chip_power_mean=jnp.mean(chip_power),
        chip_power_p95=p95(chip_power),
        envelope=envelope,
        it_power=it,
        facility_power=fac,
        ffr_active=active,
        tracking_err=track,
    )
    return rls, out


def host_mean_demand(n_hosts: int) -> np.ndarray:
    """(H,) long-run mean demand of each simulated host's archetype: the
    archetype mean, and for a bursty host its duty-weighted mean of the
    busy and idle phases."""
    mean = np.array([plant_lib._ARCHETYPES[w]["mean"] for w in
                     ("matmul", "inference", "bursty")],
                    np.float32)[_host_kinds(n_hosts)]
    bursty = _host_kinds(n_hosts) == 2
    idle = plant_lib.BURSTY_DUTY * mean + (1.0 - plant_lib.BURSTY_DUTY) \
        * plant_lib.BURSTY_LOW
    return np.where(bursty, idle, mean).astype(np.float32)


def site_scale(n_chips: int, chip_tdp: float, mw):
    """How far the simulated chips' fluctuations shrink at the site's size:
    ``sqrt(n_chips / site_chips)``, with ``site_chips = mw / chip_tdp``
    the chips a site of ``mw`` design IT MW holds.  Each simulated host
    stands for a population of independent hosts of its archetype, whose
    deviations from the archetype mean average out as one over the root
    of their number."""
    site_chips = jnp.asarray(mw, jnp.float32) * 1e6 / chip_tdp
    return jnp.minimum(jnp.sqrt(n_chips / site_chips), 1.0)


def site_demand(load, mean, scale):
    """(H,) simulated demand -> the demand of the populations they stand
    for: the archetype mean plus ``scale`` times the deviation from it."""
    return mean + scale * (load - mean)


def droop_tick(n_hosts: int, chips_per_host: int, chip_tdp: float,
               pue_design, carry, load_h, mu, band_dn, band_up, act, t_amb,
               noise_scale):
    """The 1 Hz tick under a proportional product (FCR-CE droop).

    ``act`` in [-1, 1] is the second's signed activation: the envelope
    becomes ``mu - band * act``, with the down band for ``act > 0`` and the
    up band for ``act < 0`` (``tier3.droop_bands``: each direction's PUE
    correction), and demand scales by ``frac / mu`` within [0, 1] -- duty
    is shed under-frequency and the held duty released over-frequency.
    ``load_h`` is the site's demand (:func:`site_demand`) and the chips'
    plant noise shrinks by the same ``noise_scale`` (:func:`site_scale`).
    """
    H, C = n_hosts, chips_per_host
    design_host = C * chip_tdp
    design_it_w = H * design_host
    rls, chip_power, caps, kk = carry
    kk, k1 = jax.random.split(kk)

    with jax.named_scope("engine.droop"):
        band = jnp.where(act > 0, band_dn, band_up)
        frac = mu - band * act
        envelope = frac * design_it_w
        host_env = jnp.full((H,), 1.0) * (frac * design_host)
        load_r = jnp.clip(load_h * frac / jnp.maximum(mu, 1e-3), 0.0, 1.0)

    pred = ar4_lib.predict(rls) * design_host  # (H,) W
    prev = jnp.maximum(chip_power, plant_lib.P_IDLE)
    caps = ar4_lib.host_rebalance(pred, host_env, prev, plant_lib.CAP_MIN,
                                  plant_lib.CAP_MAX)
    noise = 2.0 * noise_scale * jax.random.normal(k1, (H, C))
    target = jnp.minimum(
        plant_lib.power_model(plant_lib.F_NOMINAL, load_r[:, None]) + noise,
        caps)
    # under-frequency the deep shed may idle chips below the cap floor,
    # as in an FFR activation
    shed_target = jnp.clip(frac * chip_tdp, 53.0, caps)
    target = jnp.where(act > 0, jnp.minimum(target, shed_target), target)

    rls, out = _settle(rls, target, pred, envelope, act != 0, design_host,
                       design_it_w, pue_design, t_amb)
    return (rls, target, caps, kk), out


def _twin_scan_impl(cfg: TwinConfig, inputs: TwinInputs):
    """The 1 Hz fused update.  All (T,)-indexed inputs precomputed."""
    loads, mu_sec, rho_sec, ffr_sec, t_amb_sec, key = inputs

    def tick(carry, xs):
        load_h, mu, rho, ffr, t_amb = xs
        return twin_tick(cfg.n_hosts, cfg.chips_per_host, cfg.chip_tdp,
                         cfg.pue_design, carry, load_h, mu, rho, ffr, t_amb)

    xs = (loads, mu_sec, rho_sec, ffr_sec, t_amb_sec)
    carry0 = twin_carry_init(cfg.n_hosts, cfg.chips_per_host, key)
    _, out = jax.lax.scan(tick, carry0, xs)
    return out


_twin_scan = partial(jax.jit, static_argnames=("cfg",))(_twin_scan_impl)


@partial(jax.jit, static_argnames=("cfg",))
def _twin_scan_batch(cfg: TwinConfig, inputs: TwinInputs):
    """One compiled vmap(scan) over a leading scenario axis."""
    return jax.vmap(partial(_twin_scan_impl, cfg))(inputs)


def prepare_scenario(cfg: TwinConfig, grid: signals.GridSignals,
                     events=None, seed: int | None = None) -> TwinScenario:
    """Host-side scenario prep: Tier-3 schedule, FFR events, load traces.

    `seed` overrides cfg.seed so one TwinConfig can fan out over a seed
    batch without re-hashing the dataclass.
    """
    seed = cfg.seed if seed is None else seed
    hours = cfg.seconds // 3600
    sel = tier3_lib.Tier3Selector(pue_aware=cfg.pue_aware,
                                  pue_design=cfg.pue_design)
    op = sel.select_day(grid.ci[:hours], grid.t_amb[:hours])
    mu_h = np.atleast_1d(np.asarray(op.mu))
    rho_h = np.atleast_1d(np.asarray(op.rho))

    if events is None:
        gen = markets.FFRTriggerGen(events_per_day=4.0, seed=seed)
        events = gen.sample_day()
    ffr = np.zeros(cfg.seconds, bool)
    for (t0, _nadir, rec) in events:
        i0 = int(t0)
        ffr[i0: min(i0 + int(rec), cfg.seconds)] = True

    sec = np.arange(cfg.seconds)
    hour_idx = np.minimum(sec // 3600, hours - 1)
    mu_sec = jnp.asarray(mu_h[hour_idx], jnp.float32)
    rho_sec = jnp.asarray(rho_h[hour_idx], jnp.float32)
    t_amb_sec = jnp.asarray(grid.t_amb[hour_idx], jnp.float32)
    ffr_sec = jnp.asarray(ffr)

    key = jax.random.PRNGKey(seed)
    k_load, k_scan = jax.random.split(key)
    loads = _host_loads(cfg, k_load) * mu_sec[:, None] / 0.9
    if cfg.step_transient_amp:
        # synchronised-training power wave: every host breathes with the
        # step clock (the worst case for the grid -- no averaging across
        # desynchronised jobs), zero-mean so hourly energy is unchanged
        wave = workload_lib.step_transient(
            jnp.arange(cfg.seconds), cfg.step_period_s,
            cfg.step_transient_amp)
        loads = jnp.clip(loads * wave[:, None], 0.0, 1.0)
    inputs = TwinInputs(loads=loads, mu_sec=mu_sec, rho_sec=rho_sec,
                        ffr_sec=ffr_sec, t_amb_sec=t_amb_sec, key=k_scan)
    return TwinScenario(inputs=inputs, grid=grid, events=events,
                        mu_h=mu_h, rho_h=rho_h, seed=seed)


def stack_scenarios(scenarios: list[TwinScenario]) -> TwinInputs:
    """Stack per-scenario inputs along a new leading scenario axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[s.inputs for s in scenarios])


def summarize_twin(cfg: TwinConfig, scen: TwinScenario,
                   out: TwinMetrics) -> dict:
    """Paper Fig. 4 summary numbers for one scenario's metrics."""
    hours = cfg.seconds // 3600
    mu_h, rho_h, events, grid = scen.mu_h, scen.rho_h, scen.events, scen.grid
    warm = 60  # let RLS warm up before scoring
    err = np.asarray(out.ar4_abs_err)[warm:]
    hp = np.asarray(out.host_power)[warm:]
    design_host = cfg.chips_per_host * cfg.chip_tdp
    mae_norm = float(np.mean(err) / design_host)
    p95_norm = float(np.percentile(err, 95) / design_host)

    # FFR provision quality at the meter: delivered/committed per event
    fac = np.asarray(out.facility_power)
    it = np.asarray(out.it_power)
    qs = []
    for (t0, _n, rec) in events:
        i0 = int(t0)
        if i0 < 30 or i0 + 30 > cfg.seconds:
            continue
        pre = fac[i0 - 20: i0 - 2].mean()
        post = fac[i0 + 10: i0 + min(int(rec), 60)].mean()
        h = int(min(i0 // 3600, hours - 1))
        committed = rho_h[h] * cfg.design_it_w * cfg.pue_design
        if committed <= 0:
            continue
        qs.append(min((pre - post) / committed, 1.0))
    q_ffr = float(np.mean(qs)) if qs else float("nan")

    greenness = grid.greenness()[:hours]
    summary = dict(
        ar4_mae_norm=mae_norm,
        ar4_p95_norm=p95_norm,
        chip_power_mean=float(np.mean(np.asarray(out.chip_power_mean))),
        chip_power_p95=float(np.mean(np.asarray(out.chip_power_p95))),
        q_ffr=q_ffr,
        mean_mu_green=float(mu_h[greenness > 0.6].mean())
        if (greenness > 0.6).any() else float("nan"),
        mean_mu_dirty=float(mu_h[greenness < 0.4].mean())
        if (greenness < 0.4).any() else float("nan"),
        mean_rho=float(rho_h.mean()),
        tracking_err_mean=float(np.mean(np.asarray(out.tracking_err)[warm:])),
        it_energy_mwh=float(it.sum() / 3600.0 / 1e6),
        facility_energy_mwh=float(fac.sum() / 3600.0 / 1e6),
    )
    return summary


def run_twin(cfg: TwinConfig, grid: signals.GridSignals,
             events=None) -> tuple[TwinMetrics, dict]:
    """24 h multiscale twin on one grid.  Returns (per-second metrics, summary)."""
    scen = prepare_scenario(cfg, grid, events)
    out = _twin_scan(cfg, scen.inputs)
    return out, summarize_twin(cfg, scen, out)


def run_twin_batch(cfg: TwinConfig, scenarios: list[TwinScenario],
                   ) -> tuple[TwinMetrics, list[dict]]:
    """Replay N prepared scenarios as ONE jitted vmap(scan).

    Returns (metrics with a leading (N,) scenario axis, one summary per
    scenario).  All scenarios share `cfg` (static shapes); they may differ
    in grid, season, seed, and FFR event draw.
    """
    stacked = stack_scenarios(scenarios)
    out = _twin_scan_batch(cfg, stacked)
    summaries = [
        summarize_twin(cfg, scen, jax.tree.map(lambda x, i=i: x[i], out))
        for i, scen in enumerate(scenarios)
    ]
    return out, summaries


def net_co2_decomposition(cfg: TwinConfig, grid: signals.GridSignals,
                          summary: dict, mu_h: np.ndarray | None = None,
                          rho_h: np.ndarray | None = None) -> dict:
    """Net CO2 = Operational - Exogenous (paper Sect. 4 Metrics).

    Baseline: flat operation at the same total compute (mean mu), static
    PUE accounting, no FFR provision.  GridPilot: CI-aligned schedule +
    instantaneous PUE + avoided reserve-side emissions for the armed FFR
    band (displacing a fossil peaker at the reserve margin).
    """
    hours = cfg.seconds // 3600
    ci = grid.ci[:hours]
    t_amb = grid.t_amb[:hours]
    sel = tier3_lib.Tier3Selector(pue_aware=cfg.pue_aware,
                                  pue_design=cfg.pue_design)
    if mu_h is None or rho_h is None:
        op = sel.select_day(ci, t_amb)
        mu_h = np.asarray(op.mu)
        rho_h = np.asarray(op.rho)

    design_mw = cfg.design_it_w / 1e6
    # GridPilot operational: hourly IT = mu * design, instantaneous PUE
    it_gp = mu_h * design_mw
    pue_gp = np.asarray(pue_lib.pue(mu_h, t_amb, pue_design=cfg.pue_design))
    co2_gp = float(np.sum(it_gp * pue_gp * ci) / 1000.0)  # tCO2
    # exogenous: armed FFR band displaces spinning reserve on the LOCAL
    # grid -- a fossil peaker where fossil sets the margin (DE/IT/PL),
    # hydro/gas mix on clean grids (CH/SE).  9 % equivalent utilisation of
    # the armed band (Nordic activation statistics order).
    reserve_ci = min(650.0, 2.5 * float(np.mean(ci)) + 50.0)
    UTIL = 0.09
    exo = float(np.sum(rho_h * design_mw * cfg.pue_design * reserve_ci * UTIL)
                / 1000.0)
    # baseline: flat mu, static PUE, no reserve
    mu_flat = float(mu_h.mean())
    co2_base = float(np.sum(mu_flat * design_mw * cfg.pue_design * ci) / 1000.0)

    net_gp = co2_gp - exo
    return dict(
        co2_baseline_t=co2_base,
        co2_operational_t=co2_gp,
        co2_exogenous_t=exo,
        co2_net_t=net_gp,
        operational_savings_pct=100.0 * (co2_base - co2_gp) / co2_base,
        exogenous_savings_pct=100.0 * exo / co2_base,
        net_savings_pct=100.0 * (co2_base - net_gp) / co2_base,
    )
