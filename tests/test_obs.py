"""Observability subsystem: host-side tracer semantics, the in-graph
telemetry taps against host-side numpy oracles, and the hard gate that
``telemetry=False`` leaves the engine's compiled graph bit-identical."""
import dataclasses
import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as eng
import repro.obs.telemetry as tel_lib
from repro.grid import frequency, markets
from repro.grid.scenarios import (build_scenario_batch, frequency_seeds,
                                  product_specs)
from repro.obs import report as report_lib
from repro.obs import trace as trace_lib

CFG = eng.EngineConfig(n_hosts=3, chips_per_host=2, e_max=8,
                       events_per_day=48.0, unroll=2)


# ---------------------------------------------------------------------------
# host-side tracer
# ---------------------------------------------------------------------------


def test_span_records_nesting_and_attrs():
    tr = trace_lib.Tracer()
    with tr.span("outer", a=1):
        with tr.span("inner") as attrs:
            attrs["found"] = 42
    outer, = tr.spans("outer")
    inner, = tr.spans("inner")
    assert outer["parent"] is None and inner["parent"] == "outer"
    assert outer["attrs"] == {"a": 1}
    assert inner["attrs"]["found"] == 42
    assert outer["wall_s"] >= inner["wall_s"] >= 0.0
    # one record per span, in the order they closed, and no second copy of
    # the wall time as an observation series
    assert [r["name"] for r in tr.records] == ["inner", "outer"]
    assert tr.metrics.all_summaries() == []


def test_span_records_on_exception():
    tr = trace_lib.Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert len(tr.spans("boom")) == 1
    # the stack unwound: a new span is top-level again
    with tr.span("after"):
        pass
    assert tr.spans("after")[0]["parent"] is None


def test_records_stay_at_their_bound():
    tr = trace_lib.Tracer()
    n = trace_lib.RECORDS_MAX + 10
    for i in range(n):
        with tr.span("tick", i=i):
            pass
    tr.event("last")
    recs = tr.records
    assert len(recs) == trace_lib.RECORDS_MAX
    # the oldest dropped out, the newest are kept in order
    assert recs[0]["attrs"]["i"] == 11
    assert recs[-2]["attrs"]["i"] == n - 1 and recs[-1]["name"] == "last"


def test_spans_are_profiler_annotations(tmp_path):
    """Inside a ``jax.profiler`` trace every span is a host-plane event of
    the same name, nested as the spans were; outside one nothing is
    built."""
    from jax.profiler import ProfileData

    tr = trace_lib.Tracer()
    with trace_lib.profile(str(tmp_path)):
        with tr.span("outer"):
            with tr.span("inner"):
                time.sleep(0.001)
    path, = tmp_path.rglob("*.xplane.pb")
    got = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("outer", "inner"):
                    got[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    assert got["outer"][0] <= got["inner"][0] < got["inner"][1] \
        <= got["outer"][1]
    assert got["inner"][1] - got["inner"][0] >= 1e6
    assert tr.spans("inner")[0]["wall_s"] >= 1e-3


def test_event_returns_live_attrs_dict():
    tr = trace_lib.Tracer()
    rec = tr.event("shed", step=3)
    rec["batch_to"] = 6  # mutate after recording
    assert tr.events("shed")[0]["attrs"]["batch_to"] == 6


def test_metrics_counters_and_summary():
    m = trace_lib.Metrics()
    m.inc("n")
    m.inc("n", 2)
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    assert m.counters == {"n": 3.0}
    s = m.summary("lat")
    assert s["count"] == 4 and s["mean"] == 2.5 and s["max"] == 4.0
    assert m.summary("absent")["count"] == 0


def test_observe_many_appends_like_observe():
    m = trace_lib.Metrics()
    m.observe("lat", 0.5)
    m.observe_many("lat", np.asarray([1.0, 2.0, 3.0], np.float32))
    assert m.series("lat") == [0.5, 1.0, 2.0, 3.0]
    m.observe_many("none", np.zeros(0))
    assert [s["name"] for s in m.all_summaries()] == ["lat"]


def test_export_jsonl_roundtrip(tmp_path):
    tr = trace_lib.Tracer()
    with tr.span("phase", k="v, with comma"):
        tr.event("mark", i=1)
    tr.metrics.inc("count")
    tr.metrics.observe("obs", 7.0)
    path = tr.export_jsonl(str(tmp_path / "trace.jsonl"))
    recs = trace_lib.read_jsonl(path)
    kinds = {r["kind"] for r in recs}
    assert kinds == {"span", "event", "counter", "observation"}
    span = next(r for r in recs if r["kind"] == "span")
    assert span["name"] == "phase" and span["attrs"]["k"] == "v, with comma"
    assert "wall_s" in span


# ---------------------------------------------------------------------------
# in-graph taps vs numpy oracles
# ---------------------------------------------------------------------------


def _np_histogram(edges, x, w):
    """The oracle the jnp histogram must match: side='left' searchsorted
    bucket index + weighted bincount."""
    # float32 edges: the in-graph histogram compares in f32, and a sample
    # sitting exactly on an f32 edge must bucket identically
    idx = np.searchsorted(np.asarray(edges, np.float32),
                          np.asarray(x, np.float32), side="left")
    return np.bincount(idx, weights=np.asarray(w),
                       minlength=len(edges) + 1)


def test_histogram_matches_searchsorted_oracle():
    rng = np.random.RandomState(0)
    edges = tel_lib.TRACK_ERR_EDGES
    x = rng.lognormal(-6, 2, size=5000).astype(np.float32)
    x[:5] = np.asarray(edges[:5], np.float32)  # edge-exact values
    x[5] = np.float32(edges[0]) + 1e-6
    x[6] = np.float32(edges[0]) - 1e-6
    w = (rng.rand(5000) > 0.3).astype(np.float32)
    got = np.asarray(tel_lib.histogram(edges, x, jnp.asarray(w)))
    ref = _np_histogram(edges, x, w)
    # tolerance is float32 matmul reassociation, well below one count
    np.testing.assert_allclose(got, ref, atol=0.5)
    assert got.sum() == pytest.approx(w.sum(), abs=0.5)


def test_response_histogram_deadline_bucket_semantics():
    """t == budget is compliant: it lands at or below the 1.0-edge bucket
    (the edge IS the deadline, so compliance reads off the histogram)."""
    budget = 700.0
    t_ms = np.asarray([70.0, 700.0, 700.1, 99.0, 2000.0], np.float32)
    valid = np.asarray([1, 1, 1, 1, 0], bool)
    h = np.asarray(tel_lib.response_histogram(
        jnp.asarray(t_ms), jnp.asarray(valid), jnp.float32(budget)))
    n_under = tel_lib.RESP_FRAC_EDGES.index(1.0) + 1
    assert h.sum() == pytest.approx(4.0)       # invalid event excluded
    assert h[:n_under].sum() == pytest.approx(3.0)   # 70, 99, 700 comply
    assert h[n_under] == pytest.approx(1.0)          # 700.1 just missed
    # the paper's 97.2 ms lands in the [0.1, 0.15) bucket
    frac_972 = np.asarray(tel_lib.response_histogram(
        jnp.asarray([97.2], np.float32), jnp.asarray([True]),
        jnp.float32(700.0)))
    assert frac_972[2] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rollout():
    """Small batch rolled out with and without the taps (+ a full stack
    for the oracles)."""
    specs = product_specs(countries=("DE", "SE"), seeds=(2,), horizon_h=2,
                          products=("FFR",), reserve_rhos=(0.2,),
                          event_seeds=(3,))
    batch = build_scenario_batch(specs)
    T = int(batch.h_max) * 3600
    freq, _ = frequency.synthesize_frequency_batch(
        frequency_seeds(batch), batch.product_idx, n_seconds=T,
        events_per_day=CFG.events_per_day, max_events=CFG.max_freq_events)
    cfg_tel = dataclasses.replace(CFG, telemetry=True)
    base = eng.engine_rollout(CFG, batch, freq=freq)
    with_tel = eng.engine_rollout(cfg_tel, batch, freq=freq)
    full = eng.engine_rollout(cfg_tel, batch, reduce="full", freq=freq)
    return batch, base, with_tel, full


def test_telemetry_off_is_bit_identical(rollout):
    """The telemetry=False graph is the pre-telemetry graph: every leaf of
    the default rollout equals the telemetry run's shared leaves BIT FOR
    BIT (the taps ride the scan ys; the carried state is untouched)."""
    _, base, with_tel, _ = rollout
    shared = {k: v for k, v in with_tel.items() if k != "telemetry"}
    la, ta = jax.tree.flatten(base)
    lb, tb = jax.tree.flatten(shared)
    assert ta == tb
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_telemetry_summary_has_no_horizon_axis(rollout):
    """Telemetry output stays O(N*H + N*B): no leaf carries a T axis."""
    batch, _, with_tel, _ = rollout
    T = int(batch.h_max) * 3600
    for leaf in jax.tree.leaves(with_tel["telemetry"]):
        assert all(d != T for d in np.shape(leaf)), np.shape(leaf)


def test_telemetry_matches_host_oracle(rollout):
    """Every per-hour moment and histogram equals a numpy recomputation
    from the full per-second stacks."""
    batch, _, with_tel, full = rollout
    tel = jax.tree.map(np.asarray, with_tel["telemetry"])
    m = full["metrics"]
    N = len(batch)
    T = int(batch.h_max) * 3600
    B = T // 3600
    t = np.arange(T)
    valid_s = np.asarray(batch.hours) * 3600
    g = (t[None, :] < valid_s[:, None]).astype(np.float64)
    w = g * (t[None, :] >= CFG.warmup_s)
    n_h = g.reshape(N, B, 3600).sum(-1)
    nw_h = np.maximum(w.reshape(N, B, 3600).sum(-1), 1.0)
    np.testing.assert_allclose(tel["hour_n"], n_h, atol=1e-3)

    def rms_h(x):
        return np.sqrt((w * x * x).reshape(N, B, 3600).sum(-1) / nw_h)

    track = np.asarray(m.tracking_err, np.float64)
    np.testing.assert_allclose(tel["track_rms_h"], rms_h(track),
                               rtol=1e-4, atol=1e-6)
    design_host = CFG.chips_per_host * CFG.chip_tdp
    rls = np.asarray(m.ar4_abs_err, np.float64).mean(-1) / design_host
    np.testing.assert_allclose(tel["rls_rms_h"], rms_h(rls),
                               rtol=1e-4, atol=1e-6)
    # saturation is a fraction by construction
    assert (tel["sat_frac_h"] >= 0.0).all()
    assert (tel["sat_frac_h"] <= 1.0).all()

    # slew: exact reconstruction from the load trace + final load
    load = np.asarray(full["load_sec"], np.float64)
    nxt = np.concatenate([load[:, 1:], tel["load_final"][:, None]], axis=1)
    slew = nxt - load
    masked = np.where(g > 0, slew, -np.inf).reshape(N, B, 3600).max(-1)
    np.testing.assert_allclose(tel["slew_max_h"],
                               np.where(n_h > 0, masked, 0.0),
                               rtol=1e-4, atol=1e-6)

    # day-level tracking histogram vs the searchsorted oracle
    for i in range(N):
        ref = _np_histogram(tel_lib.TRACK_ERR_EDGES, track[i], w[i])
        np.testing.assert_allclose(tel["track_hist"][i], ref, atol=0.5)

    # response histogram vs the engine's own event surface
    ev = full["events"]
    budget = np.asarray(markets.BUDGET_MS)[np.asarray(batch.product_idx)]
    valid = np.asarray(ev.valid)
    t_full = np.asarray(ev.t_full_ms)
    assert valid.any()  # the pinned seeds must exercise the reserve path
    for i in range(N):
        ref = _np_histogram(np.asarray(tel_lib.RESP_FRAC_EDGES) * budget[i],
                            t_full[i], valid[i].astype(np.float64))
        np.testing.assert_allclose(tel["resp_hist"][i], ref, atol=1e-3)
    # compliance invariant: mass at/below the 1.0 edge IS n_budget_ok
    n_under = tel_lib.RESP_FRAC_EDGES.index(1.0) + 1
    np.testing.assert_allclose(
        tel["resp_hist"][:, :n_under].sum(-1),
        np.asarray(ev.valid & ev.budget_ok).sum(-1), atol=1e-3)
    np.testing.assert_array_equal(
        tel["n_budget_ok"], np.asarray(ev.valid & ev.budget_ok).sum(-1))
    # per-event surface: invalid slots zeroed, stats over valid only
    np.testing.assert_allclose(tel["resp_ms"],
                               np.where(valid, t_full, 0.0), atol=1e-3)
    np.testing.assert_allclose(
        tel["resp_ms_max"], np.where(valid, t_full, 0.0).max(-1), atol=1e-3)


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------


def test_report_roundtrip_and_render(rollout, tmp_path):
    _, _, with_tel, _ = rollout
    tel = jax.tree.map(np.asarray, with_tel["telemetry"])
    path = str(tmp_path / "tel.json")
    report_lib.save_telemetry(tel, path)
    loaded = report_lib.load_telemetry(path)
    np.testing.assert_allclose(loaded["resp_hist"], tel["resp_hist"])

    rows = report_lib.response_rows(loaded)
    assert rows, "expected at least one product row"
    n_events = int(np.asarray(tel["resp_valid"]).sum())
    assert sum(r["n_events"] for r in rows) == n_events
    for r in rows:
        assert 0.0 <= r["compliance"] <= 1.0
        assert r["p50_ms"] <= r["p95_ms"] <= r["max_ms"] + 1e-9

    buf = io.StringIO()
    report_lib.render_telemetry(loaded, out=buf)
    text = buf.getvalue()
    assert "deadline" in text        # the 1.0-x-budget marker line
    assert "FFR" in text             # budget resolved to a product name


def test_report_renders_trace_records():
    tr = trace_lib.Tracer()
    with tr.span("serve.decode", steps=4):
        tr.event("serve.shed", batch_from=4, batch_to=3)
    tr.metrics.inc("serve.sheds")
    buf = io.StringIO()
    report_lib.render_trace(tr.records + [
        dict(kind="counter", name="serve.sheds", value=1.0)], out=buf)
    text = buf.getvalue()
    assert "serve.decode" in text and "serve.shed" in text
