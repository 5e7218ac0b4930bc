"""Scenario-batch builder: the grid-data side of the batched sweep engine.

A *scenario* is one (country, season, seed, MW level, PUE design) replay
configuration together with its synthesised hourly CI / ambient traces.  A
:class:`ScenarioBatch` stacks N scenarios into padded device arrays with a
leading scenario axis so the whole sweep runs as ONE jitted ``vmap(scan)``
call (see ``benchmarks/e8_multicountry.py`` and
``repro.core.dispatch.replay_schedule``) instead of a Python loop of
independent replays.

Ragged horizons are supported: traces shorter than the longest one in the
batch are right-padded and masked out (``mask`` is 1.0 on valid hours), so
"as many scenarios as you can imagine" -- thousands of grid/season/seed
combos with mixed horizons -- stack into a single rectangular batch.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.pue as pue_lib
from repro.grid.markets import PRODUCT_ORDER, is_proportional
from repro.grid.signals import COUNTRY_ORDER, synthesize_ci, synthesize_t_amb
from repro.workload.model import MIX_ORDER, mix_index

DEFAULT_HORIZON_H = 28 * 24
# value padded into t_amb beyond a scenario's horizon: the calibration
# reference ambient, guaranteed in-range for every downstream PUE call.
_PAD_T_AMB = pue_lib.T_REF


@dataclass(frozen=True)
class ScenarioSpec:
    """Host-side description of one replay scenario."""

    country: str
    seed: int = 0
    start_day: int = 15          # day-of-year: season selector
    mw: float = 10.0             # site IT design power
    pue_design: float = pue_lib.PUE_DESIGN
    horizon_h: int = DEFAULT_HORIZON_H
    # reserve-market axes (the E9 seconds tier): FR product sold, committed
    # band rho (fraction of design IT power), frequency-event draw
    product: str = "FFR"
    reserve_rho: float = 0.0
    event_seed: int = 0
    # what the site is running: indexes repro.workload's mix tables (clock
    # sensitivity of the throughput curve + token rate) in settlement and
    # the workload-aware Tier-3 search
    workload_mix: str = "train"


def product_specs(countries: Sequence[str] = tuple(COUNTRY_ORDER),
                  seeds: Sequence[int] = (0,),
                  start_days: Sequence[int] = (15,),
                  mw_levels: Sequence[float] = (10.0,),
                  pue_designs: Sequence[float] = (pue_lib.PUE_DESIGN,),
                  horizon_h: int = DEFAULT_HORIZON_H,
                  products: Sequence[str] = ("FFR",),
                  reserve_rhos: Sequence[float] = (0.0,),
                  event_seeds: Sequence[int] = (0,),
                  workload_mixes: Sequence[str] = ("train",)
                  ) -> list[ScenarioSpec]:
    """Cartesian (country x season x seed x level x design x product x rho
    x event draw x workload mix) scenario grid."""
    return [
        ScenarioSpec(country=c, seed=s, start_day=d, mw=m, pue_design=pd,
                     horizon_h=horizon_h, product=p, reserve_rho=r,
                     event_seed=es, workload_mix=wm)
        for c, d, s, m, pd, p, r, es, wm in itertools.product(
            countries, start_days, seeds, mw_levels, pue_designs,
            products, reserve_rhos, event_seeds, workload_mixes)
    ]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ScenarioBatch:
    """N scenarios as padded device arrays (leading axis = scenario)."""

    country_idx: jax.Array   # (N,) int32 index into COUNTRY_ORDER
    seed: jax.Array          # (N,) int32
    start_day: jax.Array     # (N,) int32
    mw: jax.Array            # (N,) float32
    pue_design: jax.Array    # (N,) float32
    hours: jax.Array         # (N,) int32 valid trace length
    ci: jax.Array            # (N, H_max) float32, right-padded with 0
    t_amb: jax.Array         # (N, H_max) float32, right-padded with T_REF
    mask: jax.Array          # (N, H_max) float32, 1.0 on valid hours
    product_idx: jax.Array   # (N,) int32 index into markets.PRODUCT_ORDER
    reserve_rho: jax.Array   # (N,) float32 committed FR band
    event_seed: jax.Array    # (N,) int32 frequency-event draw
    mix_idx: jax.Array       # (N,) int32 index into workload.MIX_ORDER
    # static: every product of the batch is proportional (FCR-CE), not
    # triggered; the engine compiles the droop scan for it
    proportional: bool = field(default=False, metadata=dict(static=True))

    @property
    def n(self) -> int:
        return int(self.ci.shape[0])

    @property
    def h_max(self) -> int:
        return int(self.ci.shape[1])

    def __len__(self) -> int:
        return self.n

    def spec(self, i: int) -> ScenarioSpec:
        return ScenarioSpec(
            country=COUNTRY_ORDER[int(self.country_idx[i])],
            seed=int(self.seed[i]),
            start_day=int(self.start_day[i]),
            mw=float(self.mw[i]),
            pue_design=float(self.pue_design[i]),
            horizon_h=int(self.hours[i]),
            product=PRODUCT_ORDER[int(self.product_idx[i])],
            reserve_rho=float(self.reserve_rho[i]),
            event_seed=int(self.event_seed[i]),
            workload_mix=MIX_ORDER[int(self.mix_idx[i])],
        )

    def select(self, i: int) -> dict:
        """One scenario's unpadded traces as host numpy (loop/parity path)."""
        h = int(self.hours[i])
        return dict(
            spec=self.spec(i),
            ci=np.asarray(self.ci[i, :h]),
            t_amb=np.asarray(self.t_amb[i, :h]),
        )


def build_scenario_batch(specs: Sequence[ScenarioSpec],
                         h_max: int | None = None) -> ScenarioBatch:
    """Synthesize every spec's traces and stack them into one padded batch.

    Scenarios that differ only in (mw, pue_design, product, reserve_rho,
    event_seed, workload_mix) share their (country, seed, start_day,
    horizon) CI /
    ambient traces, so synthesis runs once per distinct trace key -- on
    the usual Cartesian product grids this cuts the builder's host-side
    work by the size of the non-trace axes.

    ``h_max`` overrides the padded hour axis (defaults to the longest
    horizon in ``specs``).  Streaming sweeps pass the *global* maximum so
    every chunk stacks to one shape (one compiled program); it must cover
    the longest horizon present.

    A batch holds one kind of product (:func:`product_kind`): triggered
    and proportional scenarios replay through different scans.
    """
    if not specs:
        raise ValueError("empty scenario list")
    proportional = product_kind(specs)
    h_need = max(s.horizon_h for s in specs)
    if h_max is None:
        h_max = h_need
    elif h_max < h_need:
        raise ValueError(
            f"h_max={h_max} is shorter than the longest horizon in the "
            f"spec slice ({h_need} h)")
    n = len(specs)
    ci = np.zeros((n, h_max), np.float32)
    t_amb = np.full((n, h_max), _PAD_T_AMB, np.float32)
    mask = np.zeros((n, h_max), np.float32)
    traces: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}
    for i, s in enumerate(specs):
        h = s.horizon_h
        k = (s.country, s.seed, s.start_day, h)
        if k not in traces:
            traces[k] = (synthesize_ci(s.country, h, s.seed, s.start_day),
                         synthesize_t_amb(s.country, h, s.seed, s.start_day))
        ci[i, :h], t_amb[i, :h] = traces[k]
        mask[i, :h] = 1.0
    return ScenarioBatch(
        country_idx=jnp.asarray(
            [COUNTRY_ORDER.index(s.country) for s in specs], jnp.int32),
        seed=jnp.asarray([s.seed for s in specs], jnp.int32),
        start_day=jnp.asarray([s.start_day for s in specs], jnp.int32),
        mw=jnp.asarray([s.mw for s in specs], jnp.float32),
        pue_design=jnp.asarray([s.pue_design for s in specs], jnp.float32),
        hours=jnp.asarray([s.horizon_h for s in specs], jnp.int32),
        ci=jnp.asarray(ci),
        t_amb=jnp.asarray(t_amb),
        mask=jnp.asarray(mask),
        product_idx=jnp.asarray(
            [PRODUCT_ORDER.index(s.product) for s in specs], jnp.int32),
        reserve_rho=jnp.asarray(
            [s.reserve_rho for s in specs], jnp.float32),
        event_seed=jnp.asarray([s.event_seed for s in specs], jnp.int32),
        mix_idx=jnp.asarray(
            [mix_index(s.workload_mix) for s in specs], jnp.int32),
        proportional=proportional,
    )


def product_kind(specs: Sequence[ScenarioSpec]) -> bool:
    """True if every spec sells a proportional product, False if every
    spec sells a triggered one; a mixture is refused."""
    kinds = {is_proportional(s.product) for s in specs}
    if len(kinds) > 1:
        raise ValueError(
            "a scenario batch sells either triggered or proportional "
            "products, not both: split the specs by product kind")
    return kinds.pop()


def scenario_chunk(specs: Sequence[ScenarioSpec], lo: int, hi: int, *,
                   h_max: int | None = None) -> ScenarioBatch:
    """Index-addressed chunk builder: stack specs ``[lo, hi)`` only.

    The streaming executor's batch source (``engine.engine_sweep``): each
    call synthesises and materialises ONLY its chunk's traces, so a sweep
    over millions of scenario-days -- and each process of a multi-host
    run -- never holds more than O(chunk) host or device memory; no host
    ever builds the global batch.  ``h_max`` pins the padded hour axis so
    every chunk of a sweep stacks to the same shape (one compiled
    program); it defaults to the chunk's own longest horizon.

    ``specs`` may be any random-access sequence; only ``[lo, hi)`` is
    touched.  Trace-synthesis dedup is chunk-local (scenarios sharing a
    trace key inside the chunk synthesise once).
    """
    if not (0 <= lo < hi <= len(specs)):
        raise ValueError(
            f"chunk [{lo}, {hi}) out of range for {len(specs)} specs")
    return build_scenario_batch(specs[lo:hi], h_max=h_max)


def frequency_seeds(batch: ScenarioBatch) -> jax.Array:
    """Deterministic per-scenario frequency-synthesis seed: scenarios that
    differ only in country/rho draw the same grid-event day.  Scenarios
    differing in product share event *times* but not depths (the nadir
    window is product-specific), so cross-product settlement rows compare
    product rules on similar, not identical, traces."""
    return (jnp.asarray(batch.event_seed, jnp.uint32) * 100_003
            + jnp.asarray(batch.seed, jnp.uint32))


def bidding_seeds(batch: ScenarioBatch) -> jax.Array:
    """Deterministic per-scenario seed for the Tier-3 bidding optimiser's
    forecast ensemble (``repro.optim.bidding``): decorrelated from the
    frequency-synthesis stream by a different multiplier/offset, so the
    bidder's price/CI/frequency perturbations never alias the realised
    grid-event day it is later settled against.  Same counter-based
    trace-key convention as :func:`frequency_seeds`."""
    return (jnp.asarray(batch.event_seed, jnp.uint32) * 1_000_003
            + jnp.asarray(batch.seed, jnp.uint32) * 97 + 7)


def masked_quantile_sorted(xs: jax.Array, n_valid, q: float) -> jax.Array:
    """Quantile from an ascending-sorted array whose first ``n_valid``
    entries are the valid ones (invalid sorted to +inf).  Exists so a sort
    already paid for elsewhere (e.g. schedule thresholds over the same
    trace) is reused instead of repeated -- under vmap over hundreds of
    scenarios the sorts are the sweep's dominant cost.
    """
    n_valid = jnp.asarray(n_valid)
    pos = q / 100.0 * (n_valid.astype(jnp.float32) - 1.0)
    i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, xs.shape[-1] - 1)
    i1 = jnp.clip(i0 + 1, 0, n_valid.astype(jnp.int32) - 1)
    w = pos - i0.astype(jnp.float32)
    return xs[i0] * (1.0 - w) + xs[i1] * w


def masked_quantile(x: jax.Array, mask: jax.Array, q: float) -> jax.Array:
    """Quantile of the masked entries of ``x`` (linear interpolation).

    jnp.percentile has no `where=`; this sorts invalid entries to +inf and
    interpolates at q * (n_valid - 1).  Pure jnp, vmappable.
    """
    xs = jnp.sort(jnp.where(mask > 0, x, jnp.inf))
    return masked_quantile_sorted(xs, jnp.sum(mask > 0), q)
