"""repro.service: donated-buffer SiteStore + online server.

Pins the subsystem's four load-bearing guarantees:

  * churn independence -- admitting/evicting neighbours leaves surviving
    sites' ``EngineState`` BIT-identical to an uninterrupted run,
  * no retrace -- admit/evict/storms reuse the single compiled hot tick,
  * donation -- the batched step writes back into the same device
    buffers (no per-tick allocation),
  * graceful degradation -- a stale site is quarantined alone (state
    frozen, fleet keeps ticking) and rejoins on a fresh tick; and N
    simultaneous FFR triggers each get an under-budget island response
    with no cross-site cap leakage.
"""
from __future__ import annotations

import asyncio
import socket
import time

import jax
import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.core.island import encode_trigger
from repro.obs import trace
from repro.service import (LoadGen, LoadGenConfig, ServiceConfig,
                           ServiceServer, SiteStore, demo_batch, encode_tick)

CFG = EngineConfig()


def _store(capacity, n_sites, horizon_h=1, seed=0):
    st = SiteStore(CFG, capacity, horizon_h, seed=seed)
    slots = st.admit_batch(demo_batch(n_sites, horizon_h))
    return st, slots


def _assert_lanes_equal(a, b, lanes, msg):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(la)[lanes], np.asarray(lb)[lanes], err_msg=msg)


class TestChurnBitIdentity:
    def test_admit_evict_mid_run_leaves_survivors_bit_identical(self):
        below = np.zeros(4, bool)
        below_trig = np.array([True, True, False, False])

        # uninterrupted: 2 sites, 6 ticks (trigger burst at tick 2)
        ref, _ = _store(4, 2)
        for k in range(6):
            ref.step(below_trig if k == 2 else below)
        ref_snap = ref.snapshot()

        # churned: same 2 sites, but a third admitted at tick 2 and
        # evicted at tick 4, same per-lane inputs for the survivors
        churn, _ = _store(4, 2)
        extra = demo_batch(3, 1)  # 3rd spec lands in slot 2
        for k in range(6):
            if k == 2:
                (s3,) = churn.admit_batch(
                    jax.tree.map(lambda a: a[2:3], extra))
                assert s3 == 2
            if k == 4:
                churn.evict(2)
            churn.step(below_trig if k == 2 else below)
        _assert_lanes_equal(
            ref_snap, churn.snapshot(), slice(0, 2),
            "surviving lanes diverged across admit/evict churn")

    def test_eviction_frees_and_readmission_restarts(self):
        st, slots = _store(4, 2)
        st.step()
        st.evict(slots[0])
        assert st.free_slots == 3
        (s,) = st.admit_batch(demo_batch(1, 1))
        assert s == slots[0]
        assert int(np.asarray(st.state.t)[s]) == 0  # fresh site clock
        with pytest.raises(ValueError, match="already free"):
            st.evict(3)


class TestHotPath:
    def test_no_retrace_across_churn_and_trigger_patterns(self):
        st, slots = _store(4, 2)
        SiteStore.clear_step_cache()
        st.step()
        st.step(np.array([True, False, True, False]))
        st.admit_batch(demo_batch(1, 1))
        st.step(np.ones(4, bool))
        st.evict(slots[1])
        st.step(enabled=np.array([True, False, True, True]))
        assert SiteStore.step_cache_size() == 1

    def test_step_donates_buffers_in_place(self):
        st, _ = _store(4, 2)
        st.step()  # compile
        ptr = st.state.engine.chip_power.unsafe_buffer_pointer()
        st.step()
        assert st.state.engine.chip_power.unsafe_buffer_pointer() == ptr

    def test_packed_flags_report_trigger_and_shed(self):
        st, slots = _store(4, 2)
        st.step()
        below = np.zeros(4, bool)
        below[slots[0]] = True
        enabled = np.ones(4, bool)
        enabled[slots[1]] = False
        trig, shed = np.asarray(st.step(below, enabled))
        assert trig[slots[0]] and shed[slots[0]]
        assert not trig.any(where=np.arange(4) != slots[0])
        assert not shed.any(where=np.arange(4) != slots[0])
        # the shed is served from the trigger's tick on; a disabled lane
        # below the trigger reports neither flag
        below[:] = True
        trig, shed = np.asarray(st.step(below, enabled))
        assert shed[slots[0]] and not trig[slots[0]]
        assert not trig[slots[1]] and not shed[slots[1]]

    def test_numpy_and_device_flags_tick_alike(self):
        below = [np.array([k == 1, False, True, False]) for k in range(4)]
        enabled = np.array([True, True, False, True])
        snaps = []
        for put in (np.asarray, jax.device_put):
            st, _ = _store(4, 3)
            SiteStore.clear_step_cache()
            for b in below:
                st.step(put(b), put(enabled))
            assert SiteStore.step_cache_size() == 1
            snaps.append(st.snapshot())
        _assert_lanes_equal(*snaps, slice(None),
                            "numpy and device flags ticked apart")

    def test_tick_returns_the_store_state(self):
        from repro.core.engine import EngineAccum
        from repro.service.state import StoreState, _service_step

        st, _ = _store(4, 2)
        flags = np.zeros(4, bool)
        new, packed = _service_step(st.cfg, st.sched_s, st.state, flags,
                                    flags)
        assert isinstance(new, StoreState)
        assert isinstance(new.engine.acc, EngineAccum)
        assert packed.shape == (2, 4) and packed.dtype == bool

    def test_admit_validates_capacity_and_horizon(self):
        st, _ = _store(2, 2)
        with pytest.raises(ValueError, match="free slots"):
            st.admit_batch(demo_batch(1, 1))
        st2 = SiteStore(CFG, 4, 2)
        with pytest.raises(ValueError, match="horizon"):
            st2.admit_batch(demo_batch(1, 1))


class TestServerTick:
    def test_one_fetch_per_tick(self):
        server = ServiceServer(ServiceConfig(capacity=4, horizon_h=1))
        server.admit_sites(demo_batch(3, 1))
        for _ in range(3):
            n0 = trace.metrics.counters.get("service.fetches", 0)
            server.step_once()
            assert trace.metrics.counters["service.fetches"] == n0 + 1


class TestTriggerStorm:
    def test_simultaneous_triggers_under_budget_no_leakage(self):
        cfg = ServiceConfig(capacity=8, horizon_h=1)
        server = ServiceServer(cfg)
        slots = server.admit_sites(demo_batch(8, 1))
        server.step_once()  # compile tick
        n_spans0 = len(trace.get_tracer().spans("service.ingest"))

        hit = slots[:4]
        for s in hit:
            server.ingest_trigger(s, 49.5)
        spans = trace.get_tracer().spans("service.ingest")[n_spans0:]
        assert len(spans) == len(hit)
        for rec in spans:
            assert rec["wall_s"] * 1e3 < 700.0  # FFR activation budget
        assert sorted(r["attrs"]["site"] for r in spans) == sorted(hit)

        # island register file: triggered rows shed, neighbours untouched
        np.testing.assert_array_equal(server.caps[hit],
                                      server.shed_caps[hit])
        rest = slots[4:]
        np.testing.assert_array_equal(server.caps[rest],
                                      server.armed_caps[rest])

        out = server.step_once()
        assert out["n_triggered"] == len(hit)
        assert out["n_shedding"] == len(hit)
        assert out["n_resolved"] == len(hit)

    def test_shed_release_restores_armed_caps(self):
        cfg = ServiceConfig(capacity=2, horizon_h=1)
        server = ServiceServer(cfg)
        (s0, s1) = server.admit_sites(demo_batch(2, 1))
        server.step_once()
        server.ingest_trigger(s0, 49.5)
        min_dur = int(server.store.site_tables([s0])["min_dur_s"][0])
        st = server.step_once()
        assert st["n_shedding"] == 1
        for _ in range(min_dur + 2):  # ride out the minimum duration
            st = server.step_once()
        assert st["n_shedding"] == 0
        np.testing.assert_array_equal(server.caps[s0],
                                      server.armed_caps[s0])


class TestGracefulDegradation:
    def test_stale_site_quarantined_alone_then_recovers(self):
        cfg = ServiceConfig(capacity=4, horizon_h=1, late_after_s=0.05)
        server = ServiceServer(cfg)
        slots = server.admit_sites(demo_batch(3, 1))
        server.feed_frequency(np.full(3, 50.0, np.float32), slots)
        server.step_once()

        time.sleep(0.06)  # everyone's feed is now stale...
        server.feed_frequency(np.full(2, 50.0, np.float32), slots[:2])
        t_before = np.asarray(server.store.state.t).copy()
        out = server.step_once()  # ...except the two just refreshed
        assert out["n_quarantined"] == 1
        assert out["n_run"] == 2  # no global stall
        t_after = np.asarray(server.store.state.t)
        assert t_after[slots[2]] == t_before[slots[2]]  # lane frozen
        assert all(t_after[s] == t_before[s] + 1 for s in slots[:2])

        server.feed_frequency(np.full(3, 50.0, np.float32), slots)
        out = server.step_once()  # fresh tick -> rejoin
        assert out["n_quarantined"] == 0
        assert out["n_run"] == 3
        assert trace.metrics.counters.get("service.recovered", 0) >= 1

    def test_quarantined_trigger_resolves_after_recovery(self):
        cfg = ServiceConfig(capacity=2, horizon_h=1, late_after_s=0.05)
        server = ServiceServer(cfg)
        (s0, s1) = server.admit_sites(demo_batch(2, 1))
        server.feed_frequency(np.full(2, 50.0, np.float32), [s0, s1])
        server.step_once()
        time.sleep(0.06)
        server.ingest_tick(s1, freq_hz=50.0)
        server.ingest_trigger(s0, 49.5)  # island write happens regardless
        np.testing.assert_array_equal(server.caps[s0], server.shed_caps[s0])
        out = server.step_once()
        assert out["n_quarantined"] == 1
        assert out["n_resolved"] == 0  # physics deferred, not dropped
        server.ingest_tick(s0, freq_hz=50.0)
        out = server.step_once()
        assert out["n_resolved"] == 1


class TestIngestion:
    def test_datagram_wire_formats(self):
        cfg = ServiceConfig(capacity=4, horizon_h=1)
        server = ServiceServer(cfg)
        slots = server.admit_sites(demo_batch(2, 1))
        server.ingest_datagram(encode_tick(slots[0], 49.95, 87.5, 120.0))
        assert server.freq_hz[slots[0]] == np.float32(49.95)
        assert server.price[slots[0]] == np.float32(87.5)
        assert server.ci[slots[0]] == np.float32(120.0)
        server.ingest_datagram(encode_trigger(slots[1], 49.4))
        np.testing.assert_array_equal(server.caps[slots[1]],
                                      server.shed_caps[slots[1]])
        # junk and out-of-range slots are ignored, not fatal
        server.ingest_datagram(b"nonsense")
        server.ingest_datagram(encode_trigger(99, 49.4))

    def test_udp_ingestion_through_serve_loop(self):
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as probe:
            probe.bind(("127.0.0.1", 0))
            port = probe.getsockname()[1]
        cfg = ServiceConfig(capacity=4, horizon_h=1, port=port)
        server = ServiceServer(cfg)
        slots = server.admit_sites(demo_batch(2, 1))
        server.step_once()  # compile outside the served ticks

        async def drive():
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                def on_tick(srv, k):
                    if k == 0:
                        sock.sendto(encode_trigger(slots[0], 49.5),
                                    ("127.0.0.1", port))
                        sock.sendto(encode_tick(slots[1], 50.0, 42.0, 0.0),
                                    ("127.0.0.1", port))
                    return asyncio.sleep(0.05)  # let the datagrams land
                return await server.serve(n_ticks=3, on_tick=on_tick)
            finally:
                sock.close()
                server.close()

        asyncio.run(drive())
        np.testing.assert_array_equal(server.caps[slots[0]],
                                      server.shed_caps[slots[0]])
        assert server.price[slots[1]] == np.float32(42.0)


class TestLoadGen:
    def test_drive_reports_latency_and_survives_stale_sites(self):
        cfg = ServiceConfig(capacity=8, horizon_h=1, late_after_s=0.02)
        server = ServiceServer(cfg)
        slots = server.admit_sites(demo_batch(8, 1))
        gen = LoadGen(LoadGenConfig(n_ticks=30, warmup_ticks=1,
                                    trigger_rate_per_site_day=20000.0,
                                    storm_every=10, storm_sites=4, seed=1))
        stats = asyncio.run(
            gen.drive(server, slots, stale_slots=slots[-1:]))
        assert stats["n_triggers"] > 0
        assert stats["n_resolved"] > 0
        assert stats["n_storms"] == 2
        assert 0.0 < stats["p50_trigger_to_target_ms"] <= \
            stats["p99_trigger_to_target_ms"]
        assert stats["ticks_per_s"] > 0

    def test_metrics_summary_has_p99(self):
        trace.metrics.observe("test.p99_series", 1.0)
        s = trace.metrics.summary("test.p99_series")
        assert "p99" in s and s["p99"] == 1.0


class TestSpans:
    def test_tick_spans_nest_and_hold_the_step(self):
        server = ServiceServer(ServiceConfig(capacity=8, horizon_h=1))
        slots = server.admit_sites(demo_batch(8, 1))
        server.step_once()  # compile tick
        n0 = len(trace.get_tracer().records)
        for s in slots[:2]:
            server.ingest_trigger(s, 49.5)
        out = server.step_once()
        recs = trace.get_tracer().records[n0:]
        assert [r["name"] for r in recs] == [
            "service.ingest", "service.ingest", "service.dispatch",
            "service.readback", "service.tick"]
        ingest, dispatch, readback, tick = recs[1:]
        assert ingest["parent"] is None
        assert ingest["attrs"]["site"] == slots[1]
        assert dispatch["parent"] == readback["parent"] == "service.tick"
        # service.step_ms runs from the dispatch through the read-back,
        # inside the tick
        inner = dispatch["wall_s"] + readback["wall_s"]
        assert inner <= out["step_ms"] * 1e-3 <= tick["wall_s"]

    def test_cli_profile_hook_writes_the_service_spans(self, tmp_path,
                                                       monkeypatch):
        import repro.launch.compile_cache as compile_cache
        from jax.profiler import ProfileData

        from repro.service import server as server_mod

        monkeypatch.setattr(compile_cache, "enable_compile_cache",
                            lambda: "")
        monkeypatch.setenv(trace.PROFILE_ENV, str(tmp_path))
        assert server_mod.main(["--sites", "8", "--horizon-h", "1",
                                "--ticks", "3"]) == 0
        path, = tmp_path.rglob("*.xplane.pb")
        names = {ev.name for plane in ProfileData.from_file(str(path)).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert {"service.tick", "service.dispatch",
                "service.readback"} <= names
