"""Fleet-scale streaming sweep benchmark: the ROADMAP's "millions of
scenario-days" path, exercised end-to-end.

Drives ``engine.engine_sweep`` over a >= 10^5-scenario-day scenario grid
that is never materialised as one batch: chunks are built process-locally
(``scenario_chunk``), folded into donated aggregate buffers through the
``summary_merge`` monoid, and RSS is sampled at every chunk boundary --
the constant-memory claim is asserted here AND gated in
``check_trajectory`` (steady-state growth <= FLEET_MAX_RSS_GROWTH_MB).

A seconds-tier slice (telemetry on) additionally pins streamed-vs-
monolithic parity: merging per-chunk summaries at a non-device-multiple
chunk size must match ``chunk_summary`` of one monolithic
``engine_rollout`` within FLEET_PARITY_RTOL (fp32 sum reassociation is
the only difference -- the chunking changes the order sums associate in).

``--distributed-smoke`` launches TWO coordinated ``jax.distributed``
processes against a localhost coordinator (the ``REPRO_COORD_ADDR`` env
contract).  Each worker sweeps only its ``process_slice`` of the shared
spec list -- its aggregate's ``n_scenarios`` proves it built batches for
its slice alone -- and the parent merges the raw per-process aggregates
out-of-band and checks parity against a single-process sweep.  It is a
CPU test of the host-side partitioning, never a chip run: the workers
are pinned to the CPU backend (``JAX_PLATFORMS=cpu``), and the parent
has already imported the engine stack, which would hold a chip for
itself.

    PYTHONPATH=src python -m benchmarks.engine_fleet [--fast]
    PYTHONPATH=src python -m benchmarks.engine_fleet --distributed-smoke
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import (emit, measure, peak_rss_mb, rss_mb,
                               ensure_out, timed)
import repro.core.engine as eng
from repro.grid.scenarios import product_specs

# --- gated floors (imported by benchmarks.check_trajectory) ---------------
# the streamed sweep must cover at least this many scenario-days
FLEET_MIN_SCENARIO_DAYS = 100_000
# steady-state RSS growth across the streamed sweep (MB): O(chunk), not
# O(len(specs)) -- sampled AFTER the compile+warm-up chunks
FLEET_MAX_RSS_GROWTH_MB = 64.0
# streamed-vs-monolithic relative tolerance: chunking only reassociates
# fp32 sums, so the divergence is a few ulps amplified by cancellation
FLEET_PARITY_RTOL = 5e-4

FLEET_CHUNK = 512
_WORKER_OUT = "fleet_worker_{pid}.json"


def fleet_cfg() -> eng.EngineConfig:
    """Hourly-tier config: Tier-3 search + schedule accounting per
    scenario-day.  The seconds tier at this scale is ~10^10 fused ticks
    -- a device-class run, not a CI one -- so the fleet sweep streams
    the hourly tiers and the seconds tier pins parity on a slice."""
    return eng.EngineConfig(n_hosts=2, chips_per_host=2, with_seconds=False)


def seconds_cfg() -> eng.EngineConfig:
    return eng.EngineConfig(n_hosts=2, chips_per_host=2, e_max=8,
                            events_per_day=48.0, telemetry=True)


def fleet_specs(n_days: int = FLEET_MIN_SCENARIO_DAYS):
    """A >= n_days scenario-day grid of 24 h scenarios.

    The market/site axes (MW level x product x band x workload mix = 16
    variants) share each (country, seed) weather draw, which is both the
    realistic sweep shape (compare market positions under the same
    weather) and what keeps chunk-local trace synthesis from dominating
    the stream: consecutive specs share their CI/ambient traces.
    """
    variants = 16                    # 2 mw x 2 products x 2 rhos x 2 mixes
    n_seeds = -(-n_days // (6 * variants))
    return product_specs(seeds=range(n_seeds), horizon_h=24,
                         mw_levels=(10.0, 20.0), products=("FFR", "FCR"),
                         reserve_rhos=(0.0, 0.1),
                         workload_mixes=("train", "balanced"))


def _flat_items(res: dict):
    for k, v in res.items():
        if k == "telemetry":
            for tk, tv in v.items():
                yield f"telemetry.{tk}", np.asarray(tv, np.float64)
        else:
            yield k, np.asarray(v, np.float64)


def max_rel_err(a: dict, b: dict) -> float:
    """Largest elementwise |a-b| / max(|a|, |b|, 1) over two finalized
    sweep dicts (the 1 floor keeps near-zero aggregates from exploding
    the ratio)."""
    bb = dict(_flat_items(b))
    worst = 0.0
    for k, va in _flat_items(a):
        vb = bb[k]
        err = np.abs(va - vb) / np.maximum(np.maximum(np.abs(va),
                                                      np.abs(vb)), 1.0)
        worst = max(worst, float(np.max(err)))
    return worst


def run_stream(fast: bool = False) -> dict:
    """The >= 10^5-scenario-day streamed sweep with per-chunk RSS gate."""
    cfg = fleet_cfg()
    with timed("fleet.spec_build"):
        specs = fleet_specs()
    emit("fleet.n_specs", len(specs))
    # counterfactual: what a monolithic engine_rollout over the same spec
    # list would materialise up front -- the hourly batch alone, and the
    # (N, T) frequency buffer the seconds tier would synthesise
    h = max(s.horizon_h for s in specs)
    batch_gb = len(specs) * h * 3 * 4 / 2**30
    freq_gb = len(specs) * h * 3600 * 4 / 2**30
    emit("fleet.monolith_batch_gb", round(batch_gb, 3),
         "hourly ScenarioBatch for the full spec list")
    emit("fleet.monolith_freq_gb", round(freq_gb, 1),
         "seconds-tier (N, T) frequency buffer it replaces")

    samples: list[float] = []

    def on_chunk(done, total):
        samples.append(rss_mb())

    t0 = time.perf_counter()
    res = eng.engine_sweep(cfg, specs, chunk_size=FLEET_CHUNK,
                           progress=on_chunk)
    wall = time.perf_counter() - t0
    # chunk 1 pays trace+compile; steady state starts a few chunks in
    warm = min(3, len(samples)) - 1
    growth = max(samples[warm:]) - samples[warm]
    days = res["scenario_days"]
    emit("fleet.scenario_days", days, f"streamed in {len(samples)} chunks"
         f" of {FLEET_CHUNK}")
    emit("fleet.wall_s", round(wall, 2))
    emit("fleet.days_per_s", round(days / wall, 1))
    emit("fleet.rss_growth_mb", round(growth, 1),
         f"steady-state, sampled at chunk boundaries from chunk {warm+1}")
    emit("fleet.rss_mb", round(samples[-1], 1))
    emit("fleet.peak_rss_mb", round(peak_rss_mb(), 1))
    emit("fleet.mean_mu", round(res["mean_mu"], 4))
    emit("fleet.sched_co2_t", round(res["sched_co2_t"], 1))
    assert days >= FLEET_MIN_SCENARIO_DAYS, \
        f"streamed only {days} scenario-days"
    assert growth <= FLEET_MAX_RSS_GROWTH_MB, \
        f"RSS grew {growth:.1f} MB over the stream (O(chunk) violated)"
    return res


def run_parity(fast: bool = False) -> float:
    """Seconds-tier (telemetry on) streamed-vs-monolithic parity slice."""
    cfg = seconds_cfg()
    specs = product_specs(seeds=(0, 1), horizon_h=2,
                          reserve_rhos=(0.1,),
                          workload_mixes=("train",))     # 12 scenarios
    if not fast:
        specs = specs + product_specs(seeds=(2,), horizon_h=3,
                                      reserve_rhos=(0.0, 0.2))
    from repro.grid.scenarios import build_scenario_batch
    h_max = max(s.horizon_h for s in specs)
    batch = build_scenario_batch(specs, h_max=h_max)

    def mono():
        out = eng.engine_rollout(cfg, batch)
        return eng.sweep_finalize(eng.chunk_summary(cfg, out, batch))

    ref, first_s, _ = measure("fleet.mono", mono, sync=lambda r: r)
    # chunk_size 5 is deliberately no divisor of anything: every chunk
    # exercises the padded-lane masking path
    res, stream_s, _ = measure(
        "fleet.stream", lambda: eng.engine_sweep(
            cfg, specs, chunk_size=5, h_max=h_max), sync=lambda r: r)
    err = max_rel_err(ref, res)
    emit("fleet.parity_scenarios", len(specs))
    emit("fleet.parity_max_rel_err", f"{err:.2e}",
         f"streamed(chunk=5) vs monolithic, rtol floor {FLEET_PARITY_RTOL}")
    assert err <= FLEET_PARITY_RTOL, \
        f"streamed/monolithic diverged: max rel err {err:.2e}"
    return err


def run(fast: bool = False) -> None:
    run_stream(fast=fast)
    run_parity(fast=fast)


# --- 2-process jax.distributed localhost smoke ----------------------------


def smoke_specs():
    return product_specs(seeds=range(6), horizon_h=24)      # 36 scenarios


# jax.distributed.initialize must run before ANY jax computation, and
# importing the engine stack evaluates module-level jnp constants -- so
# smoke workers are launched through this bootstrap, which initialises
# from the env contract (repro.launch.mesh imports no compute) FIRST and
# only then imports this module to run worker_main.
_WORKER_BOOT = ("import sys; "
                "from repro.launch.mesh import ensure_distributed; "
                "ensure_distributed(); "
                "from benchmarks.engine_fleet import worker_main; "
                "sys.exit(worker_main(sys.argv[1]))")


def worker_main(out_path: str) -> int:
    """One coordinated process of the distributed smoke (env contract
    already set by the parent): sweep THIS process's slice, dump the raw
    aggregate for out-of-band merging."""
    import jax
    cfg = fleet_cfg()
    specs = smoke_specs()
    from repro.launch import mesh as mesh_lib
    agg = eng.engine_sweep(cfg, specs, chunk_size=8, mesh="auto",
                           finalize=False)
    lo, hi = mesh_lib.process_slice(len(specs))
    payload = dict(
        agg={k: np.asarray(v).tolist() for k, v in agg.items()},
        lo=lo, hi=hi, n_local=hi - lo, n_total=len(specs),
        pid=jax.process_index(), n_proc=jax.process_count(),
        n_devices_local=jax.local_device_count(),
    )
    with open(out_path, "w") as f:
        json.dump(payload, f)
    return 0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_distributed_smoke(timeout_s: float = 420.0) -> None:
    """Launch 2 jax.distributed processes, merge their raw aggregates,
    and pin (a) per-process batch construction only, (b) merged parity
    with a single-process sweep."""
    out_dir = ensure_out()
    port = _free_port()
    procs, paths = [], []
    for pid in range(2):
        path = os.path.join(out_dir, _WORKER_OUT.format(pid=pid))
        if os.path.exists(path):
            os.remove(path)
        env = dict(
            os.environ,
            REPRO_COORD_ADDR=f"127.0.0.1:{port}",
            REPRO_NUM_PROCESSES="2",
            REPRO_PROCESS_ID=str(pid),
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            JAX_PLATFORMS="cpu",
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER_BOOT, path],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
        paths.append(path)
    deadline = time.time() + timeout_s
    for pid, p in enumerate(procs):
        out, _ = p.communicate(timeout=max(deadline - time.time(), 1.0))
        if p.returncode != 0:
            sys.stderr.write(out)
            raise RuntimeError(f"smoke worker {pid} exited {p.returncode}")
    workers = []
    for path in paths:
        with open(path) as f:
            workers.append(json.load(f))

    n_total = workers[0]["n_total"]
    for w in workers:
        # the proof of per-process batch construction: each process's
        # aggregate counted ONLY its slice's scenarios
        assert w["n_local"] < n_total, w
        assert int(round(w["agg"]["n_scenarios"])) == w["n_local"], w
        assert w["n_proc"] == 2, w
    assert sum(w["n_local"] for w in workers) == n_total

    merged = {k: np.asarray(v, np.float32)
              for k, v in workers[0]["agg"].items()}
    merged = eng.summary_merge(
        merged, {k: np.asarray(v, np.float32)
                 for k, v in workers[1]["agg"].items()})
    dist = eng.sweep_finalize(merged)
    ref = eng.engine_sweep(fleet_cfg(), smoke_specs(), chunk_size=8)
    err = max_rel_err(ref, dist)
    emit("fleet.dist.n_processes", 2)
    emit("fleet.dist.slices", "+".join(
        f"[{w['lo']},{w['hi']})" for w in workers),
         "per-process scenario ranges (no global batch)")
    emit("fleet.dist.parity_max_rel_err", f"{err:.2e}",
         f"2-process merged vs single-process, floor {FLEET_PARITY_RTOL}")
    assert err <= FLEET_PARITY_RTOL, \
        f"distributed merge diverged: max rel err {err:.2e}"
    emit("fleet.dist.status", "ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--distributed-smoke", action="store_true")
    args = ap.parse_args(argv)
    print("name,value,derived")
    if args.distributed_smoke:
        run_distributed_smoke()
        return 0
    run(fast=args.fast)
    return 0


if __name__ == "__main__":
    sys.exit(main())
