"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is found by name: the cell in ``BENCHMARK.json``
names its configuration (``bench/configs/<config>.json``) and its traffic
mix (``bench/traffic/<traffic>.json``); the mix names the driver that
builds, warms and drives it (``bench/drivers/<driver>.py``); each
per-layer metric is read by ``bench/metrics/<metric>.py``.  Adding a
cell, a mix or a metric adds files and entries and edits none.

A run: refuse without a TPU (or with fewer chips than the cell asks for),
place JAX's compile cache, build the inputs from ``--seed`` and warm the
cell's own shapes (set-up, ``setup_s``), drive the window, read the peak
device memory, check what the window produced against the plain
reference (``bench/reference.py``), and print one JSON line.  With
``--trace 1`` a profiler trace of the window gives the per-layer metrics
instead of the end-to-end ones.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) of the cell ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def device_gate(chips: int):
    """The devices of the run; no TPU, or too few chips, ends the process
    with the reason on stderr and nothing on stdout."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


class Context:
    """What a driver and a metric reader see of one run."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, devices):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices[:cell["chips"]]
        self.result: dict = {}
        self.profile: dict = {}


def per_layer_for(bench: dict, cell: dict) -> list[dict]:
    """The per-layer metrics this cell reports."""
    e2e_here = {m["name"] for m in end_to_end_for(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_here:
            out.append(m)
    return out


def end_to_end_for(bench: dict, cell: dict) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(args, devices=None) -> dict:
    """One run of one cell; returns the result line's object.  The tests
    pass ``devices`` to stand in for the chip."""
    bench, cell, config, traffic = find_cell(args.workload)
    if devices is None:
        devices = device_gate(cell["chips"])
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # cache every program, small ones too, so a warm set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = [0]

    def on_duration(event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py")
    ctx = Context(cell, config, traffic, args.seed, args.seconds,
                  bool(args.trace), devices)
    state = driver.setup(ctx)
    # what set-up allocated is not the window's garbage: keep the window's
    # collections to the objects the window makes, as a server does
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    c0 = compiles[0]
    pauses, t_gc = [], [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            t_gc[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - t_gc[0])

    gc.callbacks.append(on_gc)
    trace_dir = OUT / f"trace-{cell['name']}-{args.seed}"
    if ctx.trace:
        from bench import trace_reduce

        shutil.rmtree(trace_dir, ignore_errors=True)
        with jax.profiler.trace(str(trace_dir),
                                profiler_options=trace_reduce.options()):
            with jax.profiler.TraceAnnotation("bench.window"):
                ctx.result = driver.window(ctx, state)
            t_stop = time.perf_counter()
        t_written = time.perf_counter()
    else:
        ctx.result = driver.window(ctx, state)
    window_compiles = compiles[0] - c0
    gc.callbacks.remove(on_gc)
    gc.unfreeze()
    ctx.result["notes"].append(
        f"garbage collections in the window {len(pauses)}, "
        f"{sum(pauses) * 1e3:.1f} ms in all, longest "
        f"{max(pauses, default=0.0) * 1e3:.1f} ms")
    memory_peak = peak_bytes(ctx.devices)
    if ctx.trace:
        raw = trace_reduce.load(str(trace_dir))
        ctx.profile = trace_reduce.reduce(raw)
        shutil.rmtree(trace_dir, ignore_errors=True)
        walked = any(d["op_ns"] is not None for d in raw["devices"])
        ctx.result["notes"].append(
            f"trace {raw['file_bytes']} bytes, written in "
            f"{t_written - t_stop:.1f} s, read in "
            f"{time.perf_counter() - t_written:.1f} s, per-op totals "
            f"{'read' if walked else 'not read'}, device seconds by program "
            f"{ctx.profile.get('program_s')}")
    t_check = time.perf_counter()
    checks = driver.verify(ctx, state)
    del state
    ctx.result["notes"].append(
        f"the check took {time.perf_counter() - t_check:.3f} s")

    d0 = devices[0]
    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(devices), memory_peak_bytes=memory_peak)
    metrics = {}
    if ctx.trace:
        device.update(busy_s=ctx.profile.get("busy_s"),
                      window_s=ctx.profile.get("window_s"))
        for m in per_layer_for(bench, cell):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
    else:
        for m in end_to_end_for(bench, cell):
            value = (setup_s if m["name"] == "setup_s"
                     else ctx.result["e2e"].get(m["name"]))
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
    out = dict(correct=all(c["value"] <= c["limit"] for c in checks.values()),
               attempted=ctx.result["attempted"],
               failed=ctx.result["failed"], metrics=metrics, device=device)
    if ctx.trace and ctx.profile:
        out["breakdown"] = dict(device_ops=ctx.profile["device_ops"],
                                idle_gaps=ctx.profile["idle_gaps"])
    for line in ctx.result["notes"]:
        print(f"bench: {line}", file=sys.stderr)
    print(f"bench: setup_s {setup_s:.3f}, window {ctx.result['elapsed_s']:.3f}"
          f" s, compiles inside the window {window_compiles}",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
