"""Every cell of ``BENCHMARK.json`` resolves its files by name and runs a
short window at test size on the CPU, printing the contract's keys."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve_by_name(cell):
    from bench import run as bench_run

    bench, w, config, traffic = bench_run.find_cell(cell)
    assert config["name"] == w["config"]
    assert os.path.isfile(os.path.join(ROOT, "bench", "drivers",
                                       traffic["driver"] + ".py"))
    assert os.path.isfile(os.path.join(ROOT, "bench", "limits",
                                       cell + ".json"))
    for m in bench_run.per_layer_for(bench, w):
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    e2e = {m["name"] for m in bench_run.end_to_end_for(bench, w)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert bench_run.per_layer_for(bench, w)


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if BENCH["workloads"][CELLS.index(c)]
                                  ["chips"] == 1])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_a_short_window(tiny, cell, trace):
    out = tiny(cell, seed=2**31 + 11, seconds=1.0, trace=trace)
    keys = RESULT_KEYS + (["breakdown"] if trace and "breakdown" in out
                          else []) + ["checks"]
    assert list(out) == keys
    json.dumps(out)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    want = (
        {m["name"] for m in BENCH["end_to_end"]
         if cell in m.get("workloads", [cell])} if not trace else set())
    assert want <= set(out["metrics"])
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "reserve-day", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
