"""A run whose timed path is broken underneath must come out not
``correct``: for each fault a cell can have, the harness drives the rest
of the run as usual (chip look skipped, test-size traffic) and the check
has to catch it."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture
def fresh():
    """Patched functions are traced anew, and nothing patched outlives
    the test in JAX's caches."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _rollout_faults(monkeypatch, fault):
    import repro.core.engine as eng

    if fault == "state_unchanged":
        tick = eng._engine_tick

        def frozen(cfg, hp, state, xs):
            _, out = tick(cfg, hp, state, xs)
            return state, out
        monkeypatch.setattr(eng, "_engine_tick", frozen)
        return
    rollout = eng.engine_rollout

    def broken(*a, **k):
        out = dict(rollout(*a, **k))
        if fault == "altered_answer":
            i = jnp.argmax(jnp.abs(out["net_eur"]))
            out["net_eur"] = out["net_eur"].at[i].multiply(1.5)
        else:   # half the batch left out, the mean of the rest in its place
            n = out["net_eur"].shape[0]
            for key, v in out.items():
                if hasattr(v, "dtype") and jnp.issubdtype(v.dtype,
                                                          jnp.floating):
                    out[key] = v.at[n // 2:].set(jnp.mean(v[:n // 2], 0))
        return out
    monkeypatch.setattr(eng, "engine_rollout", broken)


def _sweep_faults(monkeypatch, fault):
    import repro.core.engine as eng

    if fault == "state_unchanged":
        monkeypatch.setattr(eng, "summary_merge", lambda agg, chunk: agg)
    elif fault == "half_batch":
        pad = eng._pad_chunk

        def half(batch, pad_to):
            b, lane = pad(batch, pad_to)
            n = lane.shape[0]
            return b, jnp.where(jnp.arange(n) < n // 2, 2.0 * lane, 0.0)
        monkeypatch.setattr(eng, "_pad_chunk", half)
    else:
        sweep = eng.engine_sweep

        def altered(*a, **k):
            out = dict(sweep(*a, **k))
            out["sched_co2_t"] *= 1.01
            return out
        monkeypatch.setattr(eng, "engine_sweep", altered)


def _service_faults(monkeypatch, fault):
    import repro.core.engine as eng
    import repro.service.state as state

    if fault == "state_unchanged":
        step = eng.engine_step

        def frozen(cfg, params, st, xs):
            _, out = step(cfg, params, st, xs)
            return st, out
        monkeypatch.setattr(eng, "engine_step", frozen)
    elif fault == "half_batch":
        step = state.SiteStore.step

        def half(self, below=None, enabled=None):
            enabled = np.ones(self.capacity, bool) if enabled is None \
                else np.array(enabled)
            enabled[1::2] = False
            return step(self, below, enabled)
        monkeypatch.setattr(state.SiteStore, "step", half)
    else:
        tick = state._service_step

        def altered(*a, **k):
            st, out = tick(*a, **k)
            acc = st.engine.acc
            acc = acc._replace(load=acc.load.at[0].multiply(1.5))
            return st._replace(engine=st.engine._replace(acc=acc)), out
        monkeypatch.setattr(state, "_service_step", altered)


FAULTS = ["state_unchanged", "half_batch", "altered_answer"]
CELLS = {"reserve-day": _rollout_faults, "schedule-sweep": _sweep_faults,
         "service-ffr-1024": _service_faults}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fresh, cell,
                                            fault):
    CELLS[cell](monkeypatch, fault)
    out = tiny(cell, seed=21, seconds=0.5)
    assert not out["correct"], out["checks"]


def test_a_lost_trigger_is_not_correct(tiny, monkeypatch):
    """The service's other answer: a trigger whose island write happens
    but which no tick ever applies."""
    from repro.service.server import ServiceServer

    ingest, calls = ServiceServer.ingest_trigger, [0]

    def lose_third(self, slot, freq_hz=49.5):
        calls[0] += 1
        dt = ingest(self, slot, freq_hz)
        if calls[0] == 3:
            self.pending_trig_ns[slot] = 0
        return dt
    monkeypatch.setattr(ServiceServer, "ingest_trigger", lose_third)
    out = tiny("service-ffr-1024", seed=21, seconds=0.5)
    assert out["checks"]["unresolved"]["value"] >= 1
    assert not out["correct"]


_SHARDED = textwrap.dedent("""
    import argparse, json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/bench"]
    import jax, pytest
    import bench_tiny, test_bench_faults as T
    from bench import run as R
    import repro.core.engine as eng
    import repro.launch.compile_cache as cc

    def exchange_left_out(mp):
        merge = eng.summary_merge

        def local_only(agg, chunk):
            if not isinstance(next(iter(agg.values())), jax.Array):
                return agg          # the host's merge of the chips' lanes
            return merge(agg, chunk)
        mp.setattr(eng, "summary_merge", local_only)

    got = {{}}
    for fault in sys.argv[1:]:
        jax.clear_caches()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(cc, "enable_compile_cache", lambda: "")
            mp.setattr(R, "find_cell", bench_tiny.tiny_find_cell(R.find_cell))
            if fault == "exchange_left_out":
                exchange_left_out(mp)
            elif fault != "none":
                T._sweep_faults(mp, fault)
            args = argparse.Namespace(workload="reserve-sweep-4chip", seed=4,
                                      seconds=0.5, trace=0)
            got[fault] = R.run(args, devices=jax.devices())["correct"]
    print(json.dumps(dict(correct=got, count=len(jax.devices()))))
""")
SHARDED_FAULTS = ["none", "exchange_left_out"] + FAULTS


@pytest.fixture(scope="module")
def four_cpu_devices():
    """The four-chip cell's runs, each fault in turn, in one process with
    four virtual CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _SHARDED.format(root=ROOT)]
                       + SHARDED_FAULTS, capture_output=True, text=True,
                       env=env, timeout=900, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", SHARDED_FAULTS)
def test_the_four_chip_sweep_on_four_cpu_devices(four_cpu_devices, fault):
    assert four_cpu_devices["count"] == 4
    assert four_cpu_devices["correct"][fault] == (fault == "none")
