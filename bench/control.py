"""The control of a cell's check: the plain reference computed in bfloat16,
put in the program's place and compared with the float32 reference by
the cell's own comparison.  Each number must come out above its limit
for at least one of the cell's numbers, or the check could not tell a
program that computes in the lower precision from a sound one.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 5]

Runs on the machine's accelerator at the cell's own size; the benchmark's
own runs never run it.  For the service the program first serves a short
window at the cell's load (``--seconds``), whose fed frequency flags both
references replay; the program's own numbers are printed beside the
control's, so one call gives both readings of each limit.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_numbers(driver, ctx, seed: int, seconds: float) -> dict:
    """{"program": numbers or None, "control": numbers} for one seed."""
    import jax.numpy as jnp
    import numpy as np

    kind = ctx.traffic["driver"]
    if kind == "service":
        st = driver.setup(ctx)
        ctx.result = driver.window(ctx, st)
        got = driver.program_state(st)
        st.pop("server").close()
        below = np.stack(st.pop("below"))
        ref = driver.reference_state(ctx, st, below)
        low = driver.reference_state(ctx, st, below, dt=jnp.bfloat16)
        tdp = ctx.config["engine"]["chip_tdp"]
        prog = driver.compare(got, ref, len(below), tdp)
        prog["unresolved"] = st["resolution_off"] + st["pending"]
        prog.pop("gaps")
        mu0 = low["mu0"].astype(np.float64)
        rho0 = low["rho0"].astype(np.float64)
        armed = np.clip(mu0 * tdp, 100.0, 300.0)
        shed = np.clip(np.maximum(mu0 - rho0, 0.17) * tdp, 100.0, 300.0)
        row = np.where(low["shed_last"], shed, armed).astype(np.float32)
        as_program = dict({k: v.astype(np.float64)
                           for k, v in low["acc"].items()},
                          in_event=low["in_event"], hold=low["hold"],
                          last_load=low["last_load"].astype(np.float64),
                          t=np.full(len(row), len(below)),
                          caps_row=np.repeat(row[:, None],
                                             ctx.config["engine"]["n_hosts"]
                                             * ctx.config["engine"]
                                             ["chips_per_host"], 1))
        ctl = driver.compare(as_program, ref, len(below), tdp)
        ctl["unresolved"] = 0
        ctl.pop("gaps")
        return dict(program=prog, control=ctl)
    from bench import common

    st = dict(grid=common.scenario_grid(ctx.traffic, seed))
    ref = driver.reference_outputs(ctx, st)
    low = driver.reference_outputs(ctx, st, dt=jnp.bfloat16)
    if kind == "rollout":
        return dict(program=None, control=driver.compare([low], ref))
    warm = ctx.config["engine"]["warmup_s"]
    return dict(program=None, control=driver.compare(
        [driver.aggregate(low, st["grid"], warm)],
        driver.aggregate(ref, st["grid"], warm)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run

    _, cell, config, traffic = bench_run.find_cell(args.workload)
    # only the service's control serves a window; the others run the
    # reference alone, which runs on one chip
    devices = bench_run.device_gate(
        cell["chips"] if traffic["driver"] == "service" else 1)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = bench_run.load_module(
        ROOT / "bench" / "drivers" / f"{traffic['driver']}.py")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = bench_run.Context(cell, config, traffic, seed, args.seconds,
                                False, devices)
        out = control_numbers(driver, ctx, seed, args.seconds)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              seconds=time.perf_counter() - t0, **out)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
