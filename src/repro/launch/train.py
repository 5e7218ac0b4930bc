"""Training launcher: `python -m repro.launch.train --arch smollm-135m ...`

Runs a real training loop on the local mesh (reduced config by default --
the full configs only lower on the production mesh via dryrun.py).  With
--gridpilot the GridPilot controller runs alongside: Tier-3 plans from a
synthetic grid, the safety island armed, FFR triggers shedding steps.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a big mesh); default reduced")
    ap.add_argument("--gridpilot", action="store_true")
    ap.add_argument("--grid-country", default="DE")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_local_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_local_mesh()

    gp = None
    if args.gridpilot:
        from repro.core.controller import GridPilot
        from repro.grid.signals import make_grid

        grid = make_grid(args.grid_country, 24)
        gp = GridPilot(n_hosts=1, chips_per_host=len(jax.devices()))
        plan = gp.hourly_plan(grid.ci, grid.t_amb)
        print(f"GridPilot plan: mu={plan.mu} rho={plan.rho} "
              f"(op row {gp.current_row} armed)")

    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir)
    trainer = Trainer(cfg, shape, mesh, tcfg, gridpilot=gp, seed=args.seed)
    out = trainer.train()
    losses = [h["loss"] for h in out["history"]]
    print(f"done: {len(losses)} steps, loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}, skipped {out['skipped']} (power shed)")
    if gp is not None:
        gp.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
