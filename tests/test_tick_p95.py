"""The 1 Hz tick's chip-power p95 (``twin.p95``): the top-2 fold against
``jnp.percentile`` -- exact order statistics, the value to 1 ulp, NaN,
+-inf and ties as ``jnp.percentile`` has them, under jit and vmap -- and
no sort left in the engine's ticks at the engine's chip counts."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as eng
import repro.core.twin as twin
from repro.core import EngineConfig

SHAPES = [(n,) for n in range(1, 25)] + [(2, 2), (4, 2), (1, 21), (3, 7),
                                         (100, 3)]


def _rows(shape, n_rows=64, seed=0):
    """(n_rows, *shape) float32 chip powers, with ties, NaN and +-inf."""
    n = int(np.prod(shape))
    x = np.random.default_rng(seed + n).normal(300.0, 50.0, (n_rows, n))
    x = x.astype(np.float32)
    x[1::8, 0] = x[1::8, -1]            # a tie with the last value
    x[2::8] = x[2::8, :1]               # all values tied
    x[3::8, n // 2] = np.nan
    x[4::8, 0] = np.inf
    x[5::8, -1] = -np.inf
    x[6::8, : (n + 1) // 2] = np.inf
    x[7::8] = -np.inf
    return x.reshape((n_rows,) + shape)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_p95_matches_jnp_percentile(shape):
    x = _rows(shape)
    n = int(np.prod(shape))
    flat = x.reshape(len(x), n)

    t1, t2 = jax.jit(jax.vmap(twin.top2))(x)
    srt = np.sort(flat, axis=1)               # NaN sorts last in numpy
    has_nan = np.isnan(flat).any(axis=1)
    np.testing.assert_array_equal(np.asarray(t1)[~has_nan],
                                  srt[~has_nan, -1])
    if n > 1:
        np.testing.assert_array_equal(np.asarray(t2)[~has_nan],
                                      srt[~has_nan, -2])
        assert np.isnan(np.asarray(t2)[has_nan]).all()
    assert np.isnan(np.asarray(t1)[has_nan]).all()

    got = np.asarray(jax.jit(jax.vmap(twin.p95))(x))
    ref = np.asarray(jax.jit(jax.vmap(partial(jnp.percentile, q=95.0)))(x))
    fin = np.isfinite(ref)
    np.testing.assert_array_equal(got[~fin], ref[~fin])
    ulps = np.abs(got[fin].view(np.int32).astype(np.int64)
                  - ref[fin].view(np.int32).astype(np.int64))
    assert ulps.max(initial=0) <= 1
    # one row under jit alone reads what the vmapped batch read
    for i in (0, 3, 4):
        one = np.asarray(jax.jit(twin.p95)(x[i]))
        np.testing.assert_array_equal(one, got[i])


def _engine_step_text(n_hosts, chips_per_host):
    cfg = EngineConfig(n_hosts=n_hosts, chips_per_host=chips_per_host)
    h = jnp.zeros((24,), jnp.float32)
    params = eng.EngineParams(
        mu_h=h + 0.8, rho_h=h + 0.2, t_amb_h=h + 15.0, rho_it_h=h + 0.2,
        min_dur_i=jnp.int32(5), pue_design=jnp.float32(1.2),
        clock_w=jnp.float32(0.5))
    state = eng.engine_init(cfg, jax.random.PRNGKey(0))
    xs = (jnp.full((n_hosts,), 0.7, jnp.float32), jnp.bool_(False),
          jnp.bool_(True), jnp.int32(0))
    return jax.jit(partial(eng.engine_step, cfg)).lower(
        params, state, xs).as_text()


def _tick_text(tick, n_hosts, chips_per_host, *hourly):
    carry = twin.twin_carry_init(n_hosts, chips_per_host,
                                 jax.random.PRNGKey(0))
    load = jnp.full((n_hosts,), 0.7, jnp.float32)
    fn = partial(tick, n_hosts, chips_per_host, 300.0, 1.2)
    return jax.jit(fn).lower(carry, load, *hourly).as_text()


@pytest.mark.parametrize("hc", [(2, 2), (4, 2)], ids=str)
def test_engine_step_holds_no_sort(hc):
    assert "sort" not in _engine_step_text(*hc)


def test_droop_tick_holds_no_sort():
    f = jnp.float32
    text = _tick_text(twin.droop_tick, 2, 2, f(0.8), f(0.1), f(0.1),
                      f(0.3), f(15.0), f(0.01))
    assert "sort" not in text


def test_the_standalone_twin_keeps_its_sort():
    f = jnp.float32
    text = _tick_text(twin.twin_tick, 100, 3, f(0.8), f(0.2),
                      jnp.bool_(False), f(15.0))
    assert "sort" in text
