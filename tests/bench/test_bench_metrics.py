"""Each per-layer metric's reader: what it reads from a window's result
and its reduced trace, and nothing where there is nothing to read."""
import json
import os
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PROFILE = dict(window_s=2.0, busy_s=1.5,
               program_s={"_engine_seconds_jit": 0.6, "convert": 0.1})
RESULT = dict(programs=("_engine_seconds_jit",), days=300.0, ticks=4,
              elapsed_s=0.02, tick_ms=[1.0, 2.0, 3.0, 4.0])
WANT = {
    "engine.device_ms_per_day": 0.6e3 / 300.0,
    "device.idle_share.engine": 25.0,
    "device.idle_share.service": 25.0,
    "service.tick_ms": 2.5,
    "service.tick_ms_p99": 3.97,
    "service.host_ms_per_tick": 5.0 - 2.5,
}


def _reader(name):
    from bench import run as bench_run

    return bench_run.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def test_every_declared_metric_has_a_reader():
    declared = {m["name"] for m in BENCH["per_layer"]}
    assert declared == set(WANT)
    assert all(os.path.isfile(ROOT / "bench" / "metrics" / f"{n}.py")
               for n in declared)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    ctx = SimpleNamespace(profile=PROFILE, result=RESULT)
    assert _reader(name).read(ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_with_nothing_to_read(name):
    ctx = SimpleNamespace(profile={}, result={})
    assert _reader(name).read(ctx) is None
