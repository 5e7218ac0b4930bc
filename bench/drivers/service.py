"""Driver of the online service under an open-loop trigger stream.

Set-up admits the mix's resident sites (``ServiceServer.admit_sites``),
draws the whole trigger schedule from ``--seed`` and runs a few feed-only
ticks, which compile the batched tick.  The window free-runs ticks: before
each tick every trigger whose due time has passed takes the island bypass
(``ingest_trigger``), every site gets a frequency frame (ambient noise, or
the nadir for ``dip_ticks`` ticks after a trigger), then
``ServiceServer.step_once`` advances every site.  A trigger is timed from
its due time to the end of the tick that applied it, read-back included,
so a stalled tick shows in every trigger due during the stall.  The
window closes with one draining tick that applies everything due by its
end.

The check replays every site through the plain reference with the
frequency flags the window fed, and compares the per-site state, the
island cap rows and the resolution of every trigger.
"""
from __future__ import annotations

import resource
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, reference

STATE_FLOAT = ("load", "fac", "chip_mean", "chip_p95", "thr", "shed_it",
               "track")


def schedule(traffic: dict, seed: int, seconds: float, n_sites: int):
    """(due seconds, site) of every trigger of a window, sorted by due
    time: Poisson arrivals on uniform sites plus a storm of
    ``storm_sites`` distinct sites every ``storm_every_s``."""
    rng = np.random.default_rng([abs(int(seed)), int(seed < 0), 5])
    rate = traffic["trigger_rate_per_s"]
    n = rng.poisson(rate * seconds)
    due = [np.sort(rng.uniform(0.0, seconds, n))]
    site = [rng.integers(0, n_sites, n)]
    every = traffic["storm_every_s"]
    for k in range(1, int(seconds / every) + 1):
        if k * every < seconds:
            due.append(np.full(traffic["storm_sites"], k * every))
            site.append(rng.choice(n_sites, traffic["storm_sites"],
                                   replace=False))
    due, site = np.concatenate(due), np.concatenate(site)
    order = np.argsort(due, kind="stable")
    return due[order], site[order]


def sites_of(traffic: dict, seed: int) -> list[dict]:
    seeds = common.derive(seed, "sites", traffic["n_sites"])
    c, p = traffic["countries"], traffic["products"]
    return [dict(country=c[i % len(c)], seed=seeds[i],
                 start_day=traffic["start_day"], mw=traffic["mw"],
                 pue_design=1.2, horizon_h=traffic["horizon_h"],
                 product=p[i % len(p)], rho=traffic["rho"], event_seed=0,
                 mix=traffic["mix"]) for i in range(traffic["n_sites"])]


def setup(ctx) -> dict:
    from repro.service import ServiceConfig, ServiceServer
    from repro.grid.scenarios import build_scenario_batch

    t = ctx.traffic
    sites = sites_of(t, ctx.seed)
    server = ServiceServer(ServiceConfig(
        engine=common.engine_config(ctx.config), capacity=len(sites),
        horizon_h=t["horizon_h"], tick_hz=0.0))
    slots = np.asarray(server.admit_sites(
        build_scenario_batch(common.to_specs(sites))), np.int64)
    trig_hz = np.asarray([reference.TRIGGER_HZ[reference.PRODUCTS.index(
        s["product"])] for s in sites], np.float32)
    seconds = t["trace_seconds"] if ctx.trace else ctx.seconds
    due, site = schedule(t, ctx.seed, seconds, len(sites))
    st = dict(sites=sites, server=server, slots=slots, trig_hz=trig_hz,
              due=due, site=site, end=seconds, below=[],
              feed=np.random.default_rng([abs(int(ctx.seed)), 11]),
              dip=np.zeros(len(sites), np.int64))
    for _ in range(t["warmup_ticks"]):
        tick(ctx, st, np.zeros(0, np.int64))
    return st


def tick(ctx, st, hit) -> dict:
    """Feed one frame to every site, with the triggers ``hit`` taking the
    island bypass first, then run one service tick."""
    t, srv, slots = ctx.traffic, st["server"], st["slots"]
    with jax.profiler.TraceAnnotation("bench.feed"):
        for s in hit:
            srv.ingest_trigger(int(slots[s]), t["nadir_hz"])
        st["dip"][hit] = t["dip_ticks"]
        freqs = st["feed"].normal(50.0, t["freq_sigma_hz"],
                                  len(slots)).astype(np.float32)
        freqs[st["dip"] > 0] = t["nadir_hz"]
        np.maximum(st["dip"] - 1, 0, out=st["dip"])
        srv.feed_frequency(freqs, slots)
        below = freqs < st["trig_hz"]
        below[hit] = True
        st["below"].append(below)
    with jax.profiler.TraceAnnotation("bench.tick"):
        return srv.step_once()


def window(ctx, st) -> dict:
    from repro.obs import trace

    due, site, end = st["due"], st["site"], st["end"]
    lat = np.zeros(len(due))
    late = np.zeros(len(due))
    n0 = len(trace.metrics.series("service.step_ms"))
    i = ticks = off = 0
    starts, ends = [], []
    ru0 = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        last = now >= end
        j = int(np.searchsorted(due, min(now, end), side="right"))
        late[i:j] = time.perf_counter() - t0 - due[i:j]
        starts.append(time.perf_counter() - t0)
        info = tick(ctx, st, site[i:j])
        ends.append(time.perf_counter() - t0)
        lat[i:j] = ends[-1] - due[i:j]
        off += abs(info["n_resolved"] - len(np.unique(site[i:j])))
        ticks += 1
        i = j
        if last:
            break
    elapsed = time.perf_counter() - t0
    ru1 = resource.getrusage(resource.RUSAGE_SELF)
    step_ms = trace.metrics.series("service.step_ms")[n0:]
    pending = int(np.count_nonzero(st["server"].pending_trig_ns))
    failed = off + pending
    lat_ms = np.concatenate([lat * 1e3, np.full(failed, np.inf)])

    def tail(q: float) -> float:
        # an unresolved trigger's latency is infinite; linear interpolation
        # between two of them reads NaN
        return float(np.nan_to_num(np.percentile(lat_ms, q), nan=np.inf))

    n = len(st["sites"])
    took = np.asarray(ends) - np.asarray(starts)
    k = int(np.argmax(took))
    between = np.diff(np.asarray(starts)) - took[:-1]
    notes = [
        f"longest tick {took[k] * 1e3:.3f} ms at {starts[k]:.3f} s (its "
        f"step {step_ms[k] if k < len(step_ms) else float('nan'):.3f} ms); "
        f"{int(np.sum(took > 0.02))} ticks over 20 ms; longest time "
        f"between ticks {between.max(initial=0.0) * 1e3:.3f} ms; process "
        f"CPU {ru1.ru_utime - ru0.ru_utime:.3f} s user, "
        f"{ru1.ru_stime - ru0.ru_stime:.3f} s system",
        f"{ticks} ticks x {n} sites in {elapsed:.3f} s; {len(due)} triggers "
        f"due, {failed} unresolved",
        "generator lateness (due to ingest) p50 "
        f"{np.percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late.max(initial=0.0) * 1e3:.3f} ms",
        f"trigger-to-target p50 {tail(50):.3f} ms, p95 {tail(95):.3f} ms, "
        f"p99 {tail(99):.3f} ms, max {lat_ms.max():.3f} ms"]
    st["resolution_off"], st["pending"] = off, pending
    return dict(elapsed_s=elapsed, attempted=len(due), failed=failed,
                ticks=ticks, tick_ms=step_ms, notes=notes, t0=t0, due_s=due,
                latency_s=lat,
                programs=("_service_step",),
                e2e=dict(site_seconds_per_s=n * ticks / elapsed,
                         trigger_to_target_p95_ms=tail(95)))


def program_state(st) -> dict:
    """The program's per-site state after the window, in site order, under
    the reference's names."""
    srv, slots = st["server"], st["slots"]
    snap = srv.store.snapshot()
    pick = (lambda x: np.asarray(x)[slots])
    out = {k: pick(getattr(snap.acc, k)) for k in snap.acc._fields}
    out.update(in_event=pick(snap.in_event), hold=pick(snap.hold),
               last_load=pick(snap.last_load),
               t=pick(srv.store.state.t), caps_row=srv.caps[slots].copy())
    return out


def compare(got: dict, ref: dict, n_ticks: int, tdp: float) -> dict:
    mu0 = ref["mu0"].astype(np.float64)
    rho0 = ref["rho0"].astype(np.float64)
    armed = np.clip(mu0 * tdp, reference.CAP_MIN, reference.CAP_MAX)
    shed = np.clip(np.maximum(mu0 - rho0, reference.MIN_RESIDUAL) * tdp,
                   reference.CAP_MIN, reference.CAP_MAX)
    want = np.where(ref["shed_last"], shed, armed).astype(np.float32)
    caps_off = np.any(got["caps_row"] != want[:, None], axis=1)
    acc = ref["acc"]
    shed_off = ((got["in_event"] != ref["in_event"])
                | (got["hold"] != ref["hold"])
                | (got["shed_s"] != acc["shed_s"]) | (got["t"] != n_ticks))
    gaps = {k: common.rel_gap(got[k], acc[k]) for k in STATE_FLOAT + ("err",)}
    gaps["last_load"] = common.rel_gap(got["last_load"], ref["last_load"])
    return dict(cap_rows_off=int(caps_off.sum()),
                shed_off=int(shed_off.sum()),
                state_gap=max(v for k, v in gaps.items() if k != "err"),
                rls_gap=common.median_gap(got["err"], acc["err"]),
                gaps=gaps)


def reference_state(ctx, st, below, dt=jnp.float32) -> dict:
    return reference.run_service(st["sites"], below, ctx.config["engine"],
                                 ctx.traffic["horizon_h"], dt=dt)


def verify(ctx, st) -> dict:
    got = program_state(st)
    st.pop("server").close()
    below = np.stack(st.pop("below"))
    ref = reference_state(ctx, st, below)
    values = compare(got, ref, len(below), ctx.config["engine"]["chip_tdp"])
    values["unresolved"] = st["resolution_off"] + st["pending"]
    ctx.result["notes"].append(f"gap by quantity {values.pop('gaps')}")
    return common.checks(ctx.cell["name"], values)
