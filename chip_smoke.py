"""Drive GridPilot's main paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: the phases below, in order
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One process holds the chip and runs every phase through the entry points
a user calls:

  device   JAX must report a TPU; there is no CPU fallback.
  engine   ``engine_rollout(reduce="summary")`` on the full E9 batch (288
           scenario-days x 24 h, FFR and FCR-D, four committed bands),
           then the same program on a slice of it on the host CPU: the
           settlement, energy, carbon and delivery outputs must agree
           within the sharded tests' tolerances.
  sweep    ``engine_sweep`` over the fleet bench's 10^5 hourly
           scenario-days in 512-lane chunks, as one compiled step.
  service  ``ServiceServer`` with 1024 resident sites under ``LoadGen``
           Poisson triggers and storms: every trigger resolved, p99
           trigger-to-target under the 700 ms FFR budget, one compiled
           tick.
  train    ``repro.launch.train`` with ``--arch smollm-135m --full
           --gridpilot`` for a few steps at the published width.

With ``--chips 4`` only the multi-chip path runs: the scenario-sharded
``engine_rollout`` and ``engine_sweep`` over four chips against
``mesh=None`` on one chip, with the sharded programs checked for
collectives and for work on every device.

Each phase prints its compile seconds (JAX's backend-compile events;
tracing and lowering are left out because nested jits report them once
per level) and its wall seconds, read after ``block_until_ready``.  Any
failed check makes the script exit non-zero; on success the last line of
stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import io
import json
import os
import re
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# the controlled job's batch x sequence on one 16 GB chip
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 5
SERVICE_SITES, SERVICE_TICKS = 1024, 300
PARITY_SLICE = 8
# sweep chunk of the four-chip phase (288 E9 scenarios -> 3 chunks)
SHARDED_SWEEP_CHUNK = 96

# tolerances of tests/test_engine_sharded.py: fp32 outputs at rtol 1e-3,
# the RLS-derived metrics (which amplify 1-ulp differences chaotically)
# at rtol 2e-2, detection and counts exactly
CLOSE = dict(rtol=1e-3, atol=1e-4)
RLS_RTOL = 2e-2
GROUPS = {
    "settlement": ("net_eur", "capacity_eur", "penalty_eur"),
    "energy": ("it_mwh", "fac_mwh", "sched_it_mwh", "sched_fac_mwh",
               "chip_power_mean", "mean_mu", "mean_rho"),
    "carbon": ("sched_co2_t", "sched_co2_it_t"),
    "delivery": ("events.delivered_frac",),
}
RLS_KEYS = ("ar4_mae_norm", "tracking_err_mean")
EXACT_KEYS = ("n_events", "active_s", "n_compliant", "events.t_event_s",
              "events.valid")
# what a scenario-sharded program must not contain
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def ready(tree):
    return jax.block_until_ready(tree)


def timed(fn):
    """(result, wall s, backend-compile s) of ``fn()``, blocked on its
    result before the clock is read."""
    c0, t0 = _compile_s[0], time.perf_counter()
    out = ready(fn())
    return out, time.perf_counter() - t0, _compile_s[0] - c0


def _get(out: dict, key: str):
    head, _, field = key.partition(".")
    x = out[head]
    return np.asarray(getattr(x, field) if field else x)


def _max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    den = np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-30)
    return float(np.max(np.abs(a - b) / den)) if a.size else 0.0


def compare_rollouts(phase: str, got: dict, ref: dict) -> None:
    """Hold two ``reduce="summary"`` rollouts of the same scenarios to the
    sharded tests' tolerances; print the largest relative error per
    output group."""
    for group, keys in GROUPS.items():
        worst = max(_max_rel(_get(got, k), _get(ref, k)) for k in keys)
        say(phase, f"parity {group}: max rel err {worst:.3e} "
            f"(rtol {CLOSE['rtol']}, atol {CLOSE['atol']})")
        for k in keys:
            check(np.allclose(_get(got, k), _get(ref, k), **CLOSE),
                  f"{k} outside rtol {CLOSE['rtol']}")
    worst = max(_max_rel(_get(got, k), _get(ref, k)) for k in RLS_KEYS)
    say(phase, f"parity rls: max rel err {worst:.3e} (rtol {RLS_RTOL})")
    for k in RLS_KEYS:
        check(np.allclose(_get(got, k), _get(ref, k), rtol=RLS_RTOL, atol=0),
              f"{k} outside rtol {RLS_RTOL}")
    for k in EXACT_KEYS:
        check(np.array_equal(_get(got, k), _get(ref, k)), f"{k} not exact")
    say(phase, "parity detection and counts: exact")


def check_finite(tree) -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        x = np.asarray(leaf)
        if np.issubdtype(x.dtype, np.floating):
            check(bool(np.all(np.isfinite(x))),
                  f"non-finite output {jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_engine(fast: bool = False, n_cpu: int = PARITY_SLICE) -> None:
    import repro.core.engine as eng
    from benchmarks.e9_reserve import build_e9_batch, engine_config

    specs, batch = build_e9_batch(fast=fast)
    cfg = engine_config(fast=fast)
    say("engine", f"E9 batch: {batch.n} scenarios x {batch.h_max} h "
        f"({sum(s.horizon_h for s in specs) / 24:.0f} scenario-days)")
    out, first_s, compile_s = timed(
        lambda: eng.engine_rollout(cfg, batch, reduce="summary"))
    _, again_s, _ = timed(
        lambda: eng.engine_rollout(cfg, batch, reduce="summary"))
    say("engine", f"rollout: first call {first_s:.3f} s (compile "
        f"{compile_s:.2f} s), second call {again_s:.3f} s")
    host = jax.tree.map(np.asarray, out)
    check_finite(host)
    n_events = int(host["n_events"].sum())
    valid = host["events"].valid
    say("engine", f"{n_events} events, {int(host['n_compliant'].sum())} "
        f"compliant, mean delivered frac "
        f"{float(host['events'].delivered_frac[valid].mean()):.4f}, "
        f"net {float(host['net_eur'].sum()):.1f} EUR")
    check(n_events > 0, "no reserve events detected")
    check(float(host["capacity_eur"].sum()) > 0, "nothing settled")

    idx = np.linspace(0, batch.n - 1, min(n_cpu, batch.n)).round()
    idx = idx.astype(int)
    cpu = jax.devices("cpu")[0]
    sub = jax.device_put(jax.tree.map(lambda x: np.asarray(x)[idx], batch),
                         cpu)
    with jax.default_device(cpu):
        ref, cpu_s, _ = timed(
            lambda: eng.engine_rollout(cfg, sub, reduce="summary"))
    ref = jax.tree.map(np.asarray, ref)
    say("engine", f"re-ran scenarios {idx.tolist()} on {cpu.device_kind} "
        f"in {cpu_s:.2f} s")
    compare_rollouts("engine", jax.tree.map(lambda x: x[idx], host), ref)


def phase_sweep(specs=None, chunk: int | None = None) -> None:
    import repro.core.engine as eng
    from benchmarks.engine_fleet import FLEET_CHUNK, fleet_cfg, fleet_specs

    specs = fleet_specs() if specs is None else specs
    chunk = FLEET_CHUNK if chunk is None else chunk
    n0 = eng._sweep_step_jit._cache_size()
    res = eng.engine_sweep(fleet_cfg(), specs, chunk_size=chunk)
    want_days = sum(s.horizon_h for s in specs) / 24
    n_programs = eng._sweep_step_jit._cache_size() - n0
    say("sweep", f"{res['scenario_days']:.0f} scenario-days in "
        f"{-(-len(specs) // chunk)} chunks of {chunk}, {n_programs} new "
        f"compiled step(s), mean mu {res['mean_mu']:.4f}, "
        f"sched CO2 {res['sched_co2_t']:.1f} t")
    check(res["scenario_days"] == want_days,
          f"swept {res['scenario_days']} scenario-days, want {want_days}")
    check(n_programs <= 1, f"sweep step compiled {n_programs} times")
    check(all(np.isfinite(v) for v in res.values()), "non-finite aggregate")


def phase_service(n_sites: int = SERVICE_SITES, n_ticks: int = SERVICE_TICKS,
                  horizon_h: int = 24) -> None:
    from repro.core.engine import EngineConfig
    from repro.grid import markets
    from repro.service import (LoadGen, LoadGenConfig, ServiceConfig,
                               ServiceServer, SiteStore, demo_batch)

    n0 = SiteStore.step_cache_size()
    server = ServiceServer(ServiceConfig(engine=EngineConfig(),
                                         capacity=n_sites,
                                         horizon_h=horizon_h, seed=0))
    try:
        slots, admit_s, _ = timed(lambda: server.admit_sites(
            demo_batch(n_sites, horizon_h, products=("FFR", "FCR-D"))))
        say("service", f"admitted {len(slots)} sites in {admit_s:.2f} s")
        gen = LoadGen(LoadGenConfig(
            n_ticks=n_ticks, warmup_ticks=2, trigger_rate_per_site_day=400.0,
            storm_every=max(n_ticks // 6, 1), storm_sites=64, seed=0))
        stats = asyncio.run(gen.drive(server, slots))
    finally:
        server.close()
    ready(server.store.state)
    budget = float(markets.BUDGET_MS[0])
    n_programs = SiteStore.step_cache_size() - n0
    say("service", f"{stats['ticks']} ticks x {len(slots)} sites at "
        f"{stats['ticks_per_s']:.2f} ticks/s; {stats['n_triggers']} "
        f"triggers ({stats['n_storms']} storms), {stats['n_resolved']} "
        f"resolved; trigger-to-target p50 "
        f"{stats['p50_trigger_to_target_ms']:.3f} ms, p99 "
        f"{stats['p99_trigger_to_target_ms']:.3f} ms (budget {budget:.0f} "
        f"ms); {n_programs} new compiled tick(s)")
    check(stats["n_triggers"] > 0, "load generator sent no trigger")
    check(stats["n_resolved"] == stats["n_triggers"],
          f"{stats['n_triggers'] - stats['n_resolved']} triggers unresolved")
    check(stats["p99_trigger_to_target_ms"] < budget,
          "p99 trigger-to-target over the FFR budget")
    check(n_programs <= 1, f"service tick compiled {n_programs} times")


def phase_train(full: bool = True, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS) -> None:
    from repro.launch import train

    argv = ["--arch", "smollm-135m", "--gridpilot", "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq)] + (
                ["--full"] if full else [])
    say("train", f"python -m repro.launch.train {' '.join(argv)}")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = train.main(argv)
    text = buf.getvalue()
    for line in text.splitlines():
        say("train", line.strip())
    check(rc == 0, f"train exited {rc}")
    check(re.search(r"GridPilot plan: .*\(op row \d+ armed\)", text)
          is not None, "GridPilot plan was not armed")
    done = re.search(r"done: (\d+) steps, loss (\S+) -> (\S+),", text)
    check(done is not None, "trainer printed no summary")
    n, first, last = int(done[1]), float(done[2]), float(done[3])
    check(n == steps, f"ran {n} of {steps} steps")
    check(np.isfinite(first) and np.isfinite(last), "non-finite loss")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def collectives(hlo: str) -> list[str]:
    """The collective ops named in a compiled program's text."""
    return [c for c in COLLECTIVES if c in hlo]


def phase_sharded(fast: bool = False,
                  chunk: int = SHARDED_SWEEP_CHUNK) -> None:
    import repro.core.engine as eng
    from benchmarks.e9_reserve import build_e9_batch, engine_config
    from repro.grid import frequency
    from repro.grid.scenarios import frequency_seeds, scenario_chunk
    from repro.launch.mesh import resolve_mesh

    specs, batch = build_e9_batch(fast=fast)
    cfg = engine_config(fast=fast)
    mesh = resolve_mesh("auto")
    devices = set(mesh.devices.flat)
    say("sharded", f"mesh {dict(mesh.shape)} over "
        f"{sorted(d.id for d in devices)}; E9 batch {batch.n} scenarios")

    for label, mesh_arg in (("one chip", None), ("sharded", "auto")):
        out, first_s, compile_s = timed(
            lambda: eng.engine_rollout(cfg, batch, mesh=mesh_arg))
        _, again_s, _ = timed(
            lambda: eng.engine_rollout(cfg, batch, mesh=mesh_arg))
        say("sharded", f"rollout {label}: first call {first_s:.3f} s "
            f"(compile {compile_s:.2f} s), second call {again_s:.3f} s")
        if mesh_arg is None:
            ref = out
    held = {s.device for s in out["it_mwh"].addressable_shards}
    say("sharded", f"rollout output shards on devices "
        f"{sorted(d.id for d in held)}")
    check(held == devices, "sharded rollout did not use every device")
    compare_rollouts("sharded", jax.tree.map(np.asarray, out),
                     jax.tree.map(np.asarray, ref))

    # the compiled sharded programs must keep every scenario on its device
    T = batch.h_max * 3600
    freq, _ = frequency.synthesize_frequency_batch(
        frequency_seeds(batch), batch.product_idx, n_seconds=T,
        events_per_day=cfg.events_per_day, max_events=cfg.max_freq_events)
    load_keys, scan_keys = eng.scenario_keys(batch)
    args, _ = eng.pad_scenario_axis(
        (batch, freq, None, load_keys, scan_keys, None), len(devices))
    rollout_hlo = eng._sharded_seconds_fn(cfg, "summary", mesh, False) \
        .lower(*args).compile().as_text()
    check(not collectives(rollout_hlo),
          f"sharded rollout has {collectives(rollout_hlo)}")

    res, sweep_s, compile_s = timed(
        lambda: eng.engine_sweep(cfg, specs, chunk_size=chunk, mesh="auto"))
    want = eng.sweep_finalize(eng.chunk_summary(cfg, ref, batch))
    worst = 0.0
    for k, v in want.items():
        rtol = RLS_RTOL if k in RLS_KEYS else CLOSE["rtol"]
        worst = max(worst, _max_rel(res[k], v))
        check(np.allclose(res[k], v, rtol=rtol, atol=CLOSE["atol"]),
              f"sharded sweep {k} outside rtol {rtol}")
    say("sharded", f"sweep of {res['scenario_days']:.0f} scenario-days in "
        f"chunks of {chunk}: {sweep_s:.3f} s (compile {compile_s:.2f} s); "
        f"vs the one-chip rollout: max rel err {worst:.3e} (rtol 1e-3, "
        f"RLS metrics 2e-2)")
    lanes = -(-chunk // len(devices)) * len(devices)
    step_batch, lane = eng._pad_chunk(
        scenario_chunk(specs, 0, min(chunk, len(specs)),
                       h_max=batch.h_max), lanes)
    agg = jax.tree.map(lambda x: np.zeros((len(devices),) + np.shape(x),
                                          np.float32), eng.summary_init(cfg))
    sweep_hlo = eng._sweep_step_sharded(cfg, mesh).lower(
        agg, step_batch, lane).compile().as_text()
    check(not collectives(sweep_hlo),
          f"sharded sweep step has {collectives(sweep_hlo)}")
    say("sharded", "compiled sharded rollout and sweep step: no "
        "collectives")
    for d in sorted(devices, key=lambda d: d.id):
        stats = d.memory_stats() or {}
        say("sharded", f"device {d.id} peak bytes in use "
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


# ---------------------------------------------------------------------------


def _n_files(path: str) -> int:
    return sum(len(f) for _, _, f in os.walk(path))


def device_gate(chips: int):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def run_phases(phases) -> list[str]:
    """Run each phase, print its compile and wall seconds, and return the
    names of the phases that failed (each failure's traceback goes to
    stderr; later phases still run, so one call shows every fault)."""
    failed = []
    for name, fn in phases:
        say(name, "start")
        try:
            _, wall, compile_s = timed(fn)
            say(name, f"ok: compile {compile_s:.2f} s, wall {wall:.2f} s")
        except Exception:  # noqa: BLE001 -- reported, and exits non-zero
            traceback.print_exc()
            failed.append(name)
            say(name, "FAILED")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path across four chips")
    args = ap.parse_args(argv)
    devices = device_gate(args.chips)
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    d0 = devices[0]
    say("device", f"{len(devices)} x {d0.device_kind} ({d0.platform}), "
        f"compile cache {cache_dir} holding {_n_files(cache_dir)} files")
    if args.chips == 4:
        phases = [("sharded", phase_sharded)]
    else:
        phases = [("engine", phase_engine), ("sweep", phase_sweep),
                  ("service", phase_service), ("train", phase_train)]
    failed = run_phases(phases)
    say("cache", f"{_n_files(cache_dir)} files in {cache_dir}")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
