"""Helpers the drivers share: seeds, scenario grids, gaps and limits."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


def derive(seed: int, tag: str, n: int) -> list[int]:
    """``n`` distinct non-negative int32 draws for one ``--seed``: the same
    seed gives the same list, any whole number is a valid seed."""
    rng = np.random.default_rng([abs(int(seed)), int(seed < 0),
                                 sum(tag.encode())])
    return [int(x) for x in rng.choice(2**30, size=n, replace=False)]


def scenario_grid(traffic: dict, seed: int) -> list[dict]:
    """The mix's Cartesian scenario grid, in the order the program's
    ``product_specs`` walks it (country, weather, MW, product, band,
    event draw, workload mix), with the draws taken from ``seed``."""
    weather = derive(seed, "weather", traffic["weather_draws"])
    events = derive(seed, "events", traffic["event_draws"])
    return [dict(country=c, seed=w, start_day=traffic["start_day"], mw=mw,
                 pue_design=1.2, horizon_h=traffic["horizon_h"], product=p,
                 rho=r, event_seed=e, mix=x)
            for c in traffic["countries"] for w in weather
            for mw in traffic["mw"] for p in traffic["products"]
            for r in traffic["rhos"] for e in events
            for x in traffic["mixes"]]


def to_specs(grid: list[dict]):
    """The same scenarios as the program's ``ScenarioSpec``s."""
    import repro.core  # noqa: F401  (the package resolves its cycle from here)
    from repro.grid.scenarios import ScenarioSpec

    return [ScenarioSpec(country=d["country"], seed=d["seed"],
                         start_day=d["start_day"], mw=d["mw"],
                         pue_design=d["pue_design"], horizon_h=d["horizon_h"],
                         product=d["product"], reserve_rho=d["rho"],
                         event_seed=d["event_seed"], workload_mix=d["mix"])
            for d in grid]


def engine_config(config: dict, **overrides):
    """The program's ``EngineConfig`` for a configuration file."""
    import dataclasses

    from repro.core.engine import EngineConfig

    e = config["engine"]
    cfg = EngineConfig(n_hosts=e["n_hosts"],
                       chips_per_host=e["chips_per_host"],
                       chip_tdp=e["chip_tdp"], e_max=e["e_max"],
                       events_per_day=e["events_per_day"],
                       max_freq_events=e["max_freq_events"],
                       warmup_s=e["warmup_s"])
    return dataclasses.replace(cfg, **overrides)


def rel_gap(got, want) -> float:
    """Widest gap between two stacks of one quantity, against the
    reference's own value or its median magnitude, whichever is larger
    (so a quantity that is zero in a few scenarios is still judged)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if want.size == 0:
        return 0.0
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    scale = np.where(scale > 0, scale, 1.0)
    gap = np.abs(got - want) / scale
    return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))


def median_gap(got, want) -> float:
    """Gap between the medians of two stacks of one quantity, finite
    values only, against the reference's median.  For quantities that
    depend chaotically on rounding (the RLS a-priori error: the float32
    recursion can lose positive definiteness and diverge in one scenario
    while the rest agree), where a widest gap reads noise, not a fault."""
    g = np.asarray(got, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    g, w = np.median(g[np.isfinite(g)]), np.median(w[np.isfinite(w)])
    return float(abs(g - w) / max(abs(w), 1e-30))


def limits(cell: str) -> dict:
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())


def checks(cell: str, values: dict) -> dict:
    """``{name: {value, limit}}`` for every number the cell compares."""
    lim = limits(cell)
    return {k: dict(value=values[k], limit=lim[k]) for k in lim}
