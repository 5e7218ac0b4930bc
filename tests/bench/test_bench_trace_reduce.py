"""The reduction from a profiler trace to busy time, per-program device
time and attributed idle gaps: on hand-made intervals, on an ``.xplane.pb``
written from a text description, and on a trace recorded on the CPU."""
import os
import time

import pytest

from bench import trace_reduce as tr

MS = 1_000_000   # ns


@pytest.mark.parametrize("intervals,lo,hi,want", [
    ([(0, 10), (5, 15), (20, 30)], 0, 40, 25),      # overlap counted once
    ([(0, 10), (10, 20)], 0, 40, 20),               # touching
    ([(0, 10), (2, 3), (4, 5)], 0, 40, 10),         # nested
    ([(0, 10), (20, 30)], 5, 25, 10),               # clipped to the window
    ([], 0, 10, 0),
])
def test_busy_is_the_union_of_intervals(intervals, lo, hi, want):
    assert tr.covered(intervals, lo, hi) == want


def test_gaps_go_to_the_innermost_span():
    idle = tr.gaps([(0, 10), (30, 40)], 0, 50)
    assert idle == [(10, 30), (40, 50)]
    spans = [("bench.call", 0, 45), ("bench.feed", 12, 20)]
    got = tr.attribute_gaps(idle, spans)
    assert got["bench.feed"] == pytest.approx(8e-9)
    assert got["bench.call"] == pytest.approx((20 - 8 + 5) * 1e-9)
    assert got["(no span)"] == pytest.approx(5e-9)


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, lines, names):
    body = "".join(
        f"lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 0 "
        + " ".join(_event(*e) for e in evs) + " } "
        for i, (ln, evs) in enumerate(lines))
    meta = "".join(f"event_metadata {{ key: {k} value {{ id: {k} name: "
                   f"\"{v}\" }} }} " for k, v in names.items())
    return f"planes {{ id: {pid} name: \"{name}\" {body}{meta}}}"


def _write_xplane(path):
    from jax.profiler import ProfileData

    modules = {1: "jit__engine_seconds_jit(17)", 2: "jit_convert(3)"}
    ops = {3: "%while.1 = f32[8] while(...)", 4: "%fusion.2 = f32[8] fusion"}
    # modules and ops share one metadata table per plane
    dev0 = _plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 10 * MS, 40 * MS), (2, 60 * MS, 5 * MS)]),
        ("XLA Ops", [(3, 10 * MS, 30 * MS), (4, 45 * MS, 4 * MS),
                     (4, 60 * MS, 5 * MS)])],
        {**modules, **ops})
    dev1 = _plane(2, "/device:TPU:1", [
        ("XLA Modules", [(1, 0, 100 * MS)])], modules)
    host = _plane(3, "/host:CPU", [
        ("python", [(1, 0, 100 * MS), (2, 5 * MS, 50 * MS),
                    (3, 50 * MS, 20 * MS)])],
        {1: "bench.window", 2: "bench.call", 3: "bench.feed"})
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            " ".join((dev0, dev1, host))))


def test_reduce_a_written_trace(tmp_path):
    _write_xplane(tmp_path / "t.xplane.pb")
    got = tr.reduce(tr.load(str(tmp_path)))
    assert got["n_devices"] == 2
    assert got["window_s"] == pytest.approx(0.1)
    # device 0 runs 45 ms of the window, device 1 all 100 ms
    assert got["busy_s_per_device"] == pytest.approx([0.045, 0.1])
    assert got["busy_s"] == pytest.approx(0.0725)
    # per-program time by the jitted function's name, summed over devices
    assert got["program_s"]["_engine_seconds_jit"] == pytest.approx(0.14)
    assert got["program_s"]["convert"] == pytest.approx(0.005)
    # per-op totals from the ops line, by op name
    assert dict(got["device_ops"])["%while.1"] == pytest.approx(0.03)
    assert dict(got["device_ops"])["%fusion.2"] == pytest.approx(0.009)
    # device 0 idles 0-5 (no span), 5-10 (call), 50-60 and 65-70 (feed,
    # the innermost span), 70-100 (no span); device 1 never: the mean
    idle = dict(got["idle_gaps"])
    assert idle["bench.call"] == pytest.approx(0.005 / 2)
    assert idle["bench.feed"] == pytest.approx(0.015 / 2)
    assert idle["(no span)"] == pytest.approx(0.035 / 2)


def test_annotations_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path), profiler_options=tr.options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
                time.sleep(0.002)
    got = tr.load(str(tmp_path))
    names = [a[0] for a in got["annotations"]]
    assert names.count("bench.window") == 1 and names.count("bench.call") == 3
    win = next(a for a in got["annotations"] if a[0] == "bench.window")
    for name, s, e in got["annotations"]:
        assert win[1] <= s <= e <= win[2]
    # a CPU run has no device plane: nothing to reduce, never a made-up 0
    assert got["devices"] == [] and tr.reduce(got) == {}
    assert os.path.getsize(got["path"]) == got["file_bytes"]
