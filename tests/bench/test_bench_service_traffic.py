"""The open-loop trigger stream of the service cell: one schedule per
seed, and latency taken from each trigger's due time, so a stalled tick
shows in every trigger that fell due during the stall."""
import json
import os
import time
from pathlib import Path

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
MIX = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                  "ffr-storms.json")))


def _driver():
    from bench import run as bench_run

    return bench_run.load_module(
        Path(ROOT) / "bench" / "drivers" / "service.py")


def test_same_seed_same_schedule():
    drv = _driver()
    a = drv.schedule(MIX, 2**31 + 5, 2.0, MIX["n_sites"])
    b = drv.schedule(MIX, 2**31 + 5, 2.0, MIX["n_sites"])
    c = drv.schedule(MIX, 2**31 + 6, 2.0, MIX["n_sites"])
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    due, site = a
    assert np.all(np.diff(due) >= 0) and np.all(due < 2.0)
    # Poisson at the mix's rate plus 13 storms of distinct sites
    storms = int(2.0 / MIX["storm_every_s"])
    n_storm = storms * MIX["storm_sites"]
    assert abs(len(due) - n_storm - 2.0 * MIX["trigger_rate_per_s"]) < 300
    at = due == MIX["storm_every_s"]
    assert at.sum() >= MIX["storm_sites"]
    assert len(np.unique(site[at])) >= MIX["storm_sites"]


def test_a_stalled_tick_delays_every_trigger_due_during_it(tiny, monkeypatch):
    from repro.service.state import SiteStore

    step, calls, stall = SiteStore.step, [0], []

    def slow_step(self, *a, **k):
        calls[0] += 1
        if calls[0] == 40:
            t = time.perf_counter()
            time.sleep(0.3)
            stall.append((t, time.perf_counter()))
        return step(self, *a, **k)

    monkeypatch.setattr(SiteStore, "step", slow_step)
    from bench import run as bench_run

    seen = {}
    window = None

    def keep(ctx, st):
        out = window(ctx, st)
        seen.update(out)
        return out

    drv = _driver()
    window = drv.window
    monkeypatch.setattr(drv, "window", keep)
    monkeypatch.setattr(bench_run, "load_module", lambda path: drv)
    out = tiny("service-ffr-1024", seed=7, seconds=1.5)
    assert out["correct"], out["checks"]
    (s0, s1), = stall
    t0 = seen["t0"]
    during = (seen["due_s"] >= s0 - t0) & (seen["due_s"] <= s1 - t0)
    assert during.sum() >= 5
    # each waited at least until the stall ended
    wait = seen["latency_s"][during] - (s1 - t0 - seen["due_s"][during])
    assert np.all(wait >= 0)
    assert seen["e2e"]["trigger_to_target_p95_ms"] >= 100.0
