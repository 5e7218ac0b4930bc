"""The service's own spans in a profiler trace: where they sit among the
benchmark's spans, and that laying them over the benchmark's spans moves
idle time to them and changes no other number of the reduction."""
import numpy as np
import pytest

from bench import trace_reduce as tr

MS = 1_000_000   # ns
SERVICE = ("service.tick", "service.dispatch", "service.readback",
           "service.ingest")


def _intervals(with_program: bool) -> dict:
    """Two service ticks in a 100 ms window on one device, the feed and
    the tick each under its benchmark span, the program's spans inside."""
    bench = [("bench.window", 0, 100), ("bench.feed", 0, 20),
             ("bench.tick", 20, 60), ("bench.feed", 60, 70),
             ("bench.tick", 70, 100)]
    program = [("service.ingest", 5, 8),
               ("service.tick", 21, 59), ("service.dispatch", 22, 25),
               ("service.readback", 25, 50),
               ("service.tick", 71, 99), ("service.dispatch", 72, 74),
               ("service.readback", 74, 90)]
    spans = bench + (program if with_program else [])
    modules = [("jit__service_step(7)", 30, 35),
               ("jit__service_step(7)", 80, 84)]
    return dict(
        devices=[dict(name="/device:TPU:0", op_ns=None,
                      modules=[(n, s * MS, e * MS) for n, s, e in modules])],
        annotations=[(n, s * MS, e * MS) for n, s, e in spans])


def test_program_spans_move_idle_and_nothing_else():
    plain = tr.reduce(_intervals(False))
    split = tr.reduce(_intervals(True))
    for key in ("window_s", "busy_s", "busy_s_per_device", "program_s",
                "n_devices", "device_ops"):
        assert split[key] == plain[key], key
    idle_plain = dict(plain["idle_gaps"])
    idle_split = dict(split["idle_gaps"])
    assert sum(idle_split.values()) == pytest.approx(
        sum(idle_plain.values()))
    assert idle_plain == pytest.approx({"bench.feed": 0.030,
                                        "bench.tick": 0.061})
    # each idle stretch goes to the innermost span open over it
    assert idle_split == pytest.approx({
        "bench.feed": 0.027, "service.ingest": 0.003, "bench.tick": 0.004,
        "service.tick": 0.020, "service.dispatch": 0.005,
        "service.readback": 0.032})


def _host_events(path: str) -> list[tuple[str, float, float]]:
    from jax.profiler import ProfileData

    return [ev for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in tr._events(line)
            if ev[0].startswith((tr.BENCH_PREFIX, "service."))]


def test_service_spans_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax

    from repro.obs import trace
    from repro.service import ServiceConfig, ServiceServer, demo_batch

    server = ServiceServer(ServiceConfig(capacity=8, horizon_h=1))
    slots = server.admit_sites(demo_batch(8, 1))
    server.step_once()  # compile tick
    n0 = len(trace.get_tracer().records)
    with jax.profiler.trace(str(tmp_path), profiler_options=tr.options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            for k in range(3):
                with jax.profiler.TraceAnnotation("bench.feed"):
                    for s in slots[2 * k:2 * k + 2]:
                        server.ingest_trigger(s, 49.5)
                    server.feed_frequency(np.full(8, 50.0, np.float32),
                                          slots)
                with jax.profiler.TraceAnnotation("bench.tick"):
                    server.step_once()
    got = tr.load(str(tmp_path))
    ev = _host_events(got["path"])
    names = [n for n, _, _ in ev]
    recorded = [r["name"] for r in trace.get_tracer().records[n0:]]
    for name, n in zip(SERVICE, (3, 3, 3, 6)):
        assert names.count(name) == recorded.count(name) == n, name

    def inside(name, outer):
        """Every ``name`` span lies inside one ``outer`` span."""
        outs = [(s, e) for n, s, e in ev if n == outer]
        return all(any(s0 <= s <= e <= e0 for s0, e0 in outs)
                   for n, s, e in ev if n == name)

    assert inside("service.tick", "bench.tick")
    assert inside("service.dispatch", "service.tick")
    assert inside("service.readback", "service.tick")
    assert inside("service.ingest", "bench.feed")
    # the benchmark's own reduction still reads only its own spans
    assert {n for n, _, _ in got["annotations"]} == {
        "bench.window", "bench.feed", "bench.tick"}
