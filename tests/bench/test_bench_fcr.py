"""The Continental Europe FCR cell at test size on the CPU: the program's
rollout and sweep of FCR-CE against ``bench/reference_fcr.py`` on seeded
inputs, the sharded path on four virtual devices against one, the
bfloat16 control, the ``fcr.active_share`` reader, and faults of the
droop path that the check has to catch."""
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, reference_fcr

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CELL = "fcr-ce-day"
SEED = 2**31 + 5


def _tiny():
    import bench_tiny
    from bench import run as bench_run

    return bench_tiny.tiny_find_cell(bench_run.find_cell)(CELL)


@pytest.fixture(scope="module")
def case():
    """The tiny cell's grid, the program's rollout and the reference."""
    import repro.core.engine as eng
    from repro.grid.scenarios import build_scenario_batch

    _, _, config, traffic = _tiny()
    grid = common.scenario_grid(traffic, SEED)
    specs = common.to_specs(grid)
    cfg = common.engine_config(config)
    out = jax.tree.map(np.asarray,
                       eng.engine_rollout(cfg, build_scenario_batch(specs)))
    ref = reference_fcr.run_scenarios(grid, config["engine"])
    return SimpleNamespace(config=config, grid=grid, specs=specs, cfg=cfg,
                           out=out, ref=ref)


def test_rollout_matches_the_reference(case):
    from bench.drivers import fcr

    got = fcr.compare([fcr.flatten(case.out)], case.ref)
    lim = common.limits(CELL)
    assert all(got[k] <= lim[k] for k in lim), got
    assert case.ref["n_blocks"].tolist() == [2] * len(case.grid)
    # the droop answers both ways
    assert np.all(case.out["up_s"] > 0)
    assert np.all(case.out["active_s"] > case.out["up_s"])
    assert np.all(case.out["dlv_up_mwh"] > 0)


def test_sweep_matches_the_reference(case):
    import repro.core.engine as eng

    fleet = eng.engine_sweep(case.cfg, case.specs, chunk_size=3)
    r = {k: np.asarray(v, np.float64) for k, v in case.ref.items()}
    for k in ("active_s", "up_s", "n_blocks", "n_blocks_failed"):
        assert fleet[k] == float(r[k].sum()), k
    for k in ("req_dn_mwh", "req_up_mwh", "dlv_dn_mwh", "dlv_up_mwh",
              "capacity_eur", "net_eur", "it_mwh", "fac_mwh",
              "shed_it_mwh", "tokens_mtok"):
        assert fleet[k] == pytest.approx(r[k].sum(), rel=1e-3), k
    assert fleet["block_compliance"] == pytest.approx(
        1.0 - r["n_blocks_failed"].sum() / r["n_blocks"].sum())


_SHARDED = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src"]
    import jax, numpy as np
    from bench import common
    import repro.core.engine as eng
    from repro.grid.scenarios import build_scenario_batch

    config, traffic = json.loads(sys.argv[1]), json.loads(sys.argv[2])
    specs = common.to_specs(common.scenario_grid(traffic, {seed}))
    cfg = common.engine_config(config)
    batch = build_scenario_batch(specs)
    one = jax.tree.map(np.asarray, eng.engine_rollout(cfg, batch))
    four = jax.tree.map(np.asarray, eng.engine_rollout(cfg, batch,
                                                       mesh="auto"))
    gap = {{k: float(common.rel_gap(four[k], one[k])) for k in one}}
    s1 = eng.engine_sweep(cfg, specs, chunk_size=2)
    s4 = eng.engine_sweep(cfg, specs, chunk_size=2, mesh="auto")
    sweep = {{k: abs(s4[k] - s1[k]) / max(abs(s1[k]), 1e-6) for k in s1}}
    step = eng._sweep_step_sharded(cfg, eng._resolve_mesh("auto"))
    print(json.dumps(dict(count=len(jax.devices()), rollout=gap,
                          sweep=sweep, programs=step._cache_size())))
""")


@pytest.fixture(scope="module")
def four_cpu_devices():
    _, _, config, traffic = _tiny()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", _SHARDED.format(root=ROOT, seed=SEED),
         json.dumps(config), json.dumps(traffic)],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_rollout_and_sweep_match_one_device(four_cpu_devices):
    """The same numbers to float32 reassociation; the RLS error to the
    looser tolerance of the repo's other sharded tests (its recursion
    amplifies a one-ulp difference)."""
    assert four_cpu_devices["count"] == 4
    # the sweep's first chunk runs the program every later chunk runs
    assert four_cpu_devices["programs"] == 1
    for path in ("rollout", "sweep"):
        gaps = dict(four_cpu_devices[path])
        assert gaps.pop("ar4_mae_norm") < 2e-2
        assert max(gaps.values()) < 1e-3, (path, gaps)


def test_the_bfloat16_control_is_not_correct(monkeypatch):
    import repro.launch.compile_cache as compile_cache
    from bench import control
    from bench import run as bench_run

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    _, w, config, traffic = _tiny()
    driver = bench_run.load_module(
        bench_run.BENCH / "drivers" / f"{traffic['driver']}.py")
    ctx = bench_run.Context(w, config, traffic, 31, 1.0, False,
                            jax.devices())
    got = control.control_numbers(driver, ctx, 31, 1.0)
    lim = common.limits(CELL)
    assert any(got["control"][k] > lim[k] for k in lim), got


def test_active_share_reader(monkeypatch):
    from bench import run as bench_run
    from repro.obs import trace

    reader = bench_run.load_module(
        bench_run.BENCH / "metrics" / "fcr.active_share.py")
    monkeypatch.setattr(trace, "metrics", trace.Metrics())
    ctx = SimpleNamespace(profile={}, result=dict(days=2.0))
    assert reader.read(ctx) is None            # a program without counters
    trace.metrics.inc("fcr.active_s", 0.62 * 2 * 86400)
    assert reader.read(ctx) == pytest.approx(62.0)
    assert reader.read(SimpleNamespace(profile={}, result={})) is None


@pytest.fixture
def fresh():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _faults(monkeypatch, fault):
    import repro.core.engine as eng
    import repro.core.reserve as reserve
    import repro.core.twin as twin

    if fault == "droop_sign_flipped":
        act = reserve.droop_activation
        monkeypatch.setattr(reserve, "droop_activation",
                            lambda *a: -act(*a))
    elif fault == "up_regulation_clipped":
        tick = twin.droop_tick

        def no_up(*a):
            a = list(a)
            a[9] = jnp.maximum(a[9], 0.0)        # the activation
            return tick(*a)
        monkeypatch.setattr(twin, "droop_tick", no_up)
    elif fault == "block_verdict_altered":
        rollout = eng.engine_rollout

        def altered(*a, **k):
            out = dict(rollout(*a, **k))
            out["block_ok"] = out["block_ok"].at[0, 0].set(
                ~out["block_ok"][0, 0])
            return out
        monkeypatch.setattr(eng, "engine_rollout", altered)
    else:   # the block accumulators dropped
        tick = eng._droop_tick

        def dropped(cfg, hp, state, fh, xs):
            st, _, ys = tick(cfg, hp, state, fh, xs)
            return st, fh, ys
        monkeypatch.setattr(eng, "_droop_tick", dropped)


@pytest.mark.parametrize("fault", ["droop_sign_flipped",
                                   "up_regulation_clipped",
                                   "block_verdict_altered",
                                   "block_sums_dropped"])
def test_a_broken_droop_path_is_not_correct(tiny, monkeypatch, fresh,
                                            fault):
    _faults(monkeypatch, fault)
    out = tiny(CELL, seed=21, seconds=0.5)
    assert not out["correct"], out["checks"]
