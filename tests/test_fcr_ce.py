"""The Continental Europe FCR product (FCR-CE): the droop activation, the
symmetric Tier-3 headroom, block verdicts and clawback, the OU frequency
deviation, the product constants against the deployment's configuration,
and the triggered products' scan left as it was."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as eng
import repro.core.reserve as reserve
import repro.core.tier3 as tier3
import repro.grid.frequency as frequency
import repro.grid.markets as markets
from repro.core import EngineConfig
from repro.grid import build_scenario_batch, product_specs

ROOT = os.path.join(os.path.dirname(__file__), "..")
FCR = markets.FR_PRODUCTS["FCR-CE"]
DROOP = markets.DROOP
FCR_IDX = markets.PRODUCT_ORDER.index("FCR-CE")
CFG = EngineConfig(n_hosts=2, chips_per_host=2, e_max=24,
                   events_per_day=48.0)


def _act(f):
    return np.asarray(reserve.droop_activation(
        jnp.asarray(f, jnp.float32), DROOP.deadband_hz,
        DROOP.full_activation_hz))


def test_activation_deadband_saturation_and_sign():
    f = [50.0, 50.009, 49.991, 49.99 - 0.095, 50.105, 49.8, 50.2, 49.5,
         50.6]
    a = _act(f)
    np.testing.assert_allclose(a[:3], 0.0, atol=1e-7)          # deadband
    np.testing.assert_allclose(a[3:5], [0.5, -0.5], atol=1e-4)  # midway
    np.testing.assert_allclose(a[5:], [1.0, -1.0, 1.0, -1.0], atol=1e-6)
    # under-frequency asks the site to draw less (a > 0), over-frequency more
    assert a[3] > 0 > a[4]


def test_activation_is_continuous_at_the_deadband_edge():
    f = 50.0 - np.linspace(0.0095, 0.0105, 11)
    a = _act(f)
    assert np.all(np.diff(a) >= 0) and a.max() < 3e-3


def test_symmetric_headroom():
    assert bool(tier3.headroom_ok(0.7, 0.3, symmetric=True))
    assert not bool(tier3.headroom_ok(0.8, 0.3, symmetric=True))
    assert bool(tier3.headroom_ok(0.8, 0.3))                 # shed-only
    assert not bool(tier3.headroom_ok(0.4, 0.3, symmetric=True))


@pytest.mark.parametrize("rho", [0.05, 0.1, 0.2, 0.3])
def test_tier3_keeps_headroom_both_ways(rho):
    green = jnp.linspace(0.0, 1.0, 24)
    t_amb = jnp.full((24,), 18.0)
    kw = dict(pue_aware=True, fix_rho=True, rho_fixed=rho,
              product_idx=FCR_IDX)
    sym = tier3.select_operating_points(green, t_amb, symmetric=True, **kw)
    mu = np.asarray(sym.mu)
    assert np.all(mu + rho <= 1.0 + 1e-6)
    assert np.all(mu - rho >= tier3.MIN_RESIDUAL_LOAD - 1e-6)
    shed = tier3.select_operating_points(green, t_amb, **kw)
    # the green hours run flat out when only a shed is sold
    assert float(np.max(np.asarray(shed.mu))) == pytest.approx(0.9)


def test_droop_bands_meet_the_commitment_both_ways():
    acc_dn, acc_up = tier3.droop_accuracy(0.6, 15.0, 0.2, 1.2)
    assert float(acc_dn) == pytest.approx(1.0, abs=0.02)
    assert float(acc_up) == pytest.approx(1.0, abs=0.02)
    blind_dn, blind_up = tier3.droop_accuracy(0.6, 15.0, 0.2, 1.2,
                                              pue_aware=False)
    assert float(blind_dn) != pytest.approx(float(blind_up), abs=1e-3)
    rev = tier3.revenue_score(0.6, 0.2, 15.0, FCR_IDX, pue_aware=True,
                              symmetric=True)
    assert float(rev) == pytest.approx(0.2 / tier3.RHO_MAX)


def test_block_verdicts_and_clawback_by_hand():
    hours = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    np.testing.assert_allclose(reserve.to_blocks(hours, 4), [10.0, 11.0])
    # mean |D - R| of 0.05, 0.15 and 0.1 MW against 0.1 of 1 MW committed;
    # a block with no active second complies
    err = jnp.asarray([50.0, 150.0, 100.0, 0.0])
    active = jnp.asarray([1000.0, 1000.0, 1000.0, 0.0])
    ok = np.asarray(reserve.block_verdicts(err, active, 1.0, 0.1))
    assert ok.tolist() == [True, False, True, True]
    valid = jnp.asarray([True, True, True, False])
    cap = jnp.asarray([48.0, 48.0, 48.0, 48.0])
    assert float(reserve.block_clawback(jnp.asarray(ok), valid, cap)) == 48.0
    assert float(reserve.block_clawback(jnp.zeros(4, bool), valid,
                                        cap)) == 144.0


def test_ou_sigma_and_tau_from_a_long_draw():
    x = np.asarray(frequency.ou_baseline(jax.random.PRNGKey(7), 1_000_000,
                                         DROOP.ou_sigma_hz, DROOP.ou_tau_s),
                   np.float64) - 50.0
    assert x.std() == pytest.approx(DROOP.ou_sigma_hz, rel=0.05)
    r1 = np.corrcoef(x[:-1], x[1:])[0, 1]
    assert -1.0 / np.log(r1) == pytest.approx(DROOP.ou_tau_s, rel=0.1)
    # about 62 % of seconds fall outside the 10 mHz deadband
    assert np.mean(np.abs(x) > DROOP.deadband_hz) == pytest.approx(0.617,
                                                                 abs=0.03)


def test_product_constants_match_the_configuration():
    with open(os.path.join(ROOT, "bench", "configs",
                           "continental-fcr.json")) as f:
        conf = json.load(f)
    assert conf["product"] == "FCR-CE" and FCR.droop is DROOP
    assert markets.is_proportional("FCR-CE")
    assert not markets.is_proportional("FCR")
    g, a = conf["guarantees"], conf["assumed"]
    assert DROOP.deadband_hz * 1e3 == pytest.approx(g["insensitivity_mhz"])
    assert DROOP.full_activation_hz * 1e3 == pytest.approx(
        g["full_activation_mhz"])
    assert FCR.activation_budget_ms == 1e3 * g["full_activation_time_s"]
    assert DROOP.block_h == g["block_h"]
    assert DROOP.ou_sigma_hz == a["ou_sigma_hz"]
    assert DROOP.ou_tau_s == a["ou_tau_s"]
    assert DROOP.tracking_tol == a["tracking_tol"]
    assert FCR.capacity_price_eur_mw_h == a["capacity_price_eur_mw_h"]
    # appended: no existing product index moved
    assert markets.PRODUCT_ORDER[:5] == ("FFR", "FCR-D", "FCR", "aFRR",
                                         "mFRR")


def test_a_mixed_batch_is_refused():
    specs = product_specs(countries=("DE",), horizon_h=4,
                          products=("FFR", "FCR-CE"))
    with pytest.raises(ValueError, match="triggered or proportional"):
        build_scenario_batch(specs)
    with pytest.raises(ValueError, match="triggered or proportional"):
        eng.engine_sweep(CFG, specs, chunk_size=2)


# engine_rollout of the triggered products on this batch, as the tree
# before the proportional product computed it (float32, on the CPU)
TRIGGERED_BEFORE = {
    "n_events": [3, 3, 3, 3, 3, 3, 3, 3],
    "n_compliant": [3, 2, 3, 2, 3, 3, 3, 3],
    "active_s": [596, 596, 1088, 1088, 596, 596, 1088, 1088],
    "net_eur": [0.0, -534.7266235351562, 0.0, -177.57089233398438, 0.0,
                202.2723388671875, 0.0, 80.56725311279297],
    "tracking_err_mean": [0.12168451398611069, 0.1152806207537651,
                          0.12174861133098602, 0.11005620658397675,
                          0.12234144657850266, 0.12058527022600174,
                          0.12236584722995758, 0.1191515401005745],
    "it_mwh": [11.183221817016602, 10.902838706970215, 11.182780265808105,
               10.670953750610352, 11.18463134765625, 10.93327522277832,
               11.184216499328613, 10.725601196289062],
    "shed_it_mwh": [0.0, 0.3807796239852905, 0.0, 0.695107102394104, 0.0,
                    0.3492409884929657, 0.0, 0.6375431418418884],
}


def _triggered_batch():
    return build_scenario_batch(product_specs(
        countries=("DE", "SE"), horizon_h=2, reserve_rhos=(0.0, 0.2),
        products=("FFR", "FCR-D"), event_seeds=(3,)))


def test_triggered_rollout_is_unchanged():
    out = eng.engine_rollout(CFG, _triggered_batch())
    for k, want in TRIGGERED_BEFORE.items():
        np.testing.assert_allclose(np.asarray(out[k]), want, rtol=1e-6,
                                   err_msg=k)


def _scan_text(batch):
    freq, _ = frequency.synthesize_frequency_batch(
        eng.frequency_seeds(batch), batch.product_idx,
        n_seconds=batch.h_max * 3600, events_per_day=CFG.events_per_day,
        max_events=CFG.max_freq_events, proportional=batch.proportional)
    lk, sk = eng.scenario_keys(batch)
    return eng._engine_seconds_jit.lower(
        CFG, "summary", batch, freq, None, lk, sk).as_text(debug_info=True)


def test_only_the_proportional_scan_holds_droop_ops():
    triggered = _scan_text(_triggered_batch())
    assert "engine.droop" not in triggered
    assert "engine.fcr_blocks" not in triggered
    fcr = _scan_text(build_scenario_batch(product_specs(
        countries=("DE",), horizon_h=1, reserve_rhos=(0.2,),
        products=("FCR-CE",))))
    assert "engine.droop" in fcr and "engine.fcr_blocks" in fcr


def test_counters_are_published_after_the_rollout():
    from repro.obs import trace

    batch = build_scenario_batch(product_specs(
        countries=("DE",), horizon_h=4, reserve_rhos=(0.1, 0.2),
        products=("FCR-CE",), event_seeds=(3,)))
    out = eng.engine_rollout(CFG, batch)
    before = trace.metrics.counters
    got = eng.publish_fcr_counters(out)
    after = trace.metrics.counters
    assert got["fcr.blocks"] == 2 and got["fcr.blocks_failed"] >= 0
    assert 0 < got["fcr.up_s"] < got["fcr.active_s"] <= 2 * 4 * 3600
    for name, v in got.items():
        assert after[name] == pytest.approx(before.get(name, 0.0) + v)


def test_full_mode_keeps_the_seconds_and_summary_keeps_none():
    batch = build_scenario_batch(product_specs(
        countries=("FR",), horizon_h=1, reserve_rhos=(0.2,),
        products=("FCR-CE",), event_seeds=(3,)))
    full = eng.engine_rollout(CFG, batch, reduce="full")
    summary = eng.engine_rollout(CFG, batch)
    assert full["act"].shape == (1, 3600)
    assert full["metrics"].it_power.shape == (1, 3600)
    assert summary["declared_mw_h"].shape == (1, 1)
    assert all(np.ndim(v) <= 2 and np.shape(v)[-1:] != (3600,)
               for v in summary.values())
    np.testing.assert_allclose(full["net_eur"], summary["net_eur"])
    # the meter moves against the activation around the hour's declared
    # baseline: less draw under-frequency, more over-frequency
    a = np.asarray(full["act"][0])
    resp = (float(full["declared_mw_h"][0, 0]) / 10.0
            - np.asarray(full["metrics"].facility_power[0])
            / CFG.design_it_w)
    assert np.corrcoef(a, resp)[0, 1] > 0.9
    assert abs(np.mean(resp[a == 0])) < 2e-3


def test_the_site_stands_for_its_hosts():
    """A 10 MW site of 300 W chips holds 33,333 of them: the 2 x 2
    simulated chips' deviations shrink by sqrt(4 / 33,333), and the
    declared baseline is the hosts' mean demand through the plant."""
    import repro.core.twin as twin

    s = float(twin.site_scale(4, 300.0, 10.0))
    assert s == pytest.approx(np.sqrt(4 * 300.0 / 10e6))
    assert float(twin.site_scale(4, 300.0, 1e-4)) == 1.0
    mean = twin.host_mean_demand(2)
    np.testing.assert_allclose(mean, [0.97, 0.5 * 0.95 + 0.5 * 0.05])
    load = jnp.asarray([[0.5, 0.2], [1.0, 0.9]])
    np.testing.assert_allclose(twin.site_demand(load, mean, 0.1),
                               mean + 0.1 * (np.asarray(load) - mean),
                               rtol=1e-6)
    mu = jnp.asarray([0.4, 0.9])
    dec = np.asarray(eng._declared_fac(CFG, mu, jnp.asarray([18.0, 18.0]),
                                       1.2))
    # at mu 0.9 the matmul host fills its envelope share, the bursty host
    # does not; the meter adds the overhead
    assert 0.4 < dec[0] < dec[1] < 0.9 * 1.2
