"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``load(trace_dir)`` reads the ``.xplane.pb`` that ``jax.profiler.trace``
wrote, with ``jax.profiler.ProfileData`` alone:

  devices      one entry per ``/device:<kind>:<n>`` plane: the intervals of
               its ``XLA Modules`` line (one per program execution, named
               ``jit_<function>(<id>)``) and, where the trace is small
               enough to walk, the per-op totals of its ``XLA Ops`` line,
  annotations  the benchmark's own host spans (``jax.profiler.
               TraceAnnotation`` names starting with ``bench.``).

Every time is in nanoseconds on the trace's one clock.  The functions
below work on plain ``(name, start_ns, end_ns)`` tuples, so the tests
check them on hand-made intervals and on a trace recorded on the CPU.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

BENCH_PREFIX = "bench."
# walking every op of a long scan costs seconds per million events; past
# this file size only the program-level line is read
MAX_OPS_FILE_BYTES = 96 << 20


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` pairs covering the same time."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the union of ``intervals``."""
    return sum(e - s for s, e in clip(union(intervals), lo, hi))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no busy interval covers."""
    out, t = [], lo
    for s, e in clip(union(busy), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute_gaps(idle, annotations) -> dict[str, float]:
    """Split each idle stretch among the innermost benchmark spans that
    overlap it; time under no span goes to ``(no span)``.  Returns
    seconds per span name."""
    spans = sorted(annotations, key=lambda a: a[2] - a[1])   # innermost first
    out: dict[str, float] = defaultdict(float)
    for g0, g1 in idle:
        left = [(g0, g1)]
        for name, s, e in spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    out[name] += (hi - lo) * 1e-9
                    if lo > a:
                        nxt.append((a, lo))
                    if b > hi:
                        nxt.append((hi, b))
                else:
                    nxt.append((a, b))
            left = nxt
        for a, b in left:
            out["(no span)"] += (b - a) * 1e-9
    return dict(out)


def program_name(module: str) -> str:
    """``jit__sweep_step_jit(123)`` -> ``_sweep_step_jit``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def options():
    """The profiler's options for a traced window: no Python function
    tracing, which doubles a host-bound window's time, and no HLO protos;
    the reduction reads only the programs, their ops and the bench spans."""
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def load(trace_dir: str, device_prefix: str = "/device:TPU:") -> dict:
    """The reduced trace of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    path = files[-1]
    walk_ops = os.path.getsize(path) <= MAX_OPS_FILE_BYTES
    data = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            dev = dict(name=plane.name, modules=[], op_ns=None)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = list(_events(line))
                elif line.name == "XLA Ops" and walk_ops:
                    tot: dict[str, float] = defaultdict(float)
                    for name, s, e in _events(line):
                        tot[name.split(" = ", 1)[0]] += e - s
                    dev["op_ns"] = dict(tot)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations += [ev for ev in _events(line)
                                if ev[0].startswith(BENCH_PREFIX)]
    return dict(path=path, file_bytes=os.path.getsize(path), devices=devices,
                annotations=annotations)


def reduce(tr: dict, window: str = "bench.window") -> dict:
    """Busy time, per-program device time and attributed idle gaps over
    the benchmark's window span (the longest span of that name)."""
    wins = [a for a in tr["annotations"] if a[0] == window]
    if not wins or not tr["devices"]:
        return {}
    _, lo, hi = max(wins, key=lambda a: a[2] - a[1])
    spans = [a for a in tr["annotations"] if a[0] != window]
    busy, programs, idle = [], defaultdict(float), defaultdict(float)
    ops: dict[str, float] = defaultdict(float)
    for dev in tr["devices"]:
        iv = [(s, e) for _, s, e in dev["modules"]]
        busy.append(covered(iv, lo, hi) * 1e-9)
        for name, s, e in dev["modules"]:
            programs[program_name(name)] += sum(
                b - a for a, b in clip([(s, e)], lo, hi)) * 1e-9
        for name, sec in attribute_gaps(gaps(iv, lo, hi), spans).items():
            idle[name] += sec / len(tr["devices"])
        for name, ns in (dev["op_ns"] or {}).items():
            ops[name] += ns * 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    if not top_ops:
        top_ops = sorted(programs.items(), key=lambda kv: -kv[1])[:10]
    return dict(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy),
        busy_s_per_device=busy,
        program_s=dict(programs),
        n_devices=len(tr["devices"]),
        device_ops=[[n, s] for n, s in top_ops],
        idle_gaps=[[n, s] for n, s in
                   sorted(idle.items(), key=lambda kv: -kv[1])[:10]])
