"""Central-finite-difference vs ``jax.grad`` for every Tier-3 objective
term and the full ensemble settlement objective.

A silent gradient bug in the bidding optimiser would corrupt every
downstream commitment, so the check is strict: float64 (via
``jax.enable_x64`` -- the objective stack follows input
dtype) with a max relative error of 1e-3 for every term, parameterised
over ALL ``PRODUCT_ORDER`` products and both ``pue_aware`` settings.
Check points sit in the interior of each term's smooth pieces (the hard
terms are piecewise-differentiable; the optimiser's smooth surrogate is
checked at and around the MIN_RESIDUAL_LOAD boundary, where the
gradient must be finite and nonzero on both sides).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.tier3 as tier3
import repro.grid.markets as markets
from repro.optim import bidding

REL_TOL = 1e-3
PRODUCTS = list(range(len(markets.PRODUCT_ORDER)))
AWARE = [True, False]


def fd_vs_ad(f, x0: float, h: float = 1e-6) -> float:
    """Max-relative-error between jax.grad and a central difference,
    both evaluated in float64."""
    with jax.enable_x64(True):
        x = jnp.float64(x0)
        ad = float(jax.grad(f)(x))
        fd = float((f(x + h) - f(x - h)) / (2.0 * h))
    assert np.isfinite(ad) and np.isfinite(fd)
    return abs(ad - fd) / max(abs(ad), abs(fd), 1e-6)


@pytest.mark.parametrize("pue_aware", AWARE)
@pytest.mark.parametrize("product_idx", PRODUCTS)
def test_q_ffr_grad(product_idx, pue_aware):
    del product_idx  # q_ffr is product-free; keep the full matrix anyway
    base = {"mu": 0.7, "rho": 0.2, "t_amb": 15.0}
    for wrt, x0 in base.items():
        def f(v):
            a = {k: jnp.float64(x) for k, x in base.items()}
            a[wrt] = v
            return tier3.q_ffr(a["mu"], a["rho"], a["t_amb"],
                               pue_aware=pue_aware)
        assert fd_vs_ad(f, x0) < REL_TOL, (wrt, pue_aware)


@pytest.mark.parametrize("pue_aware", AWARE)
@pytest.mark.parametrize("product_idx", PRODUCTS)
def test_revenue_score_grad(product_idx, pue_aware):
    def f_mu(mu):
        return tier3.revenue_score(mu, jnp.float64(0.15), jnp.float64(15.0),
                                   product_idx, pue_aware=pue_aware)

    def f_rho(rho):
        return tier3.revenue_score(jnp.float64(0.8), rho, jnp.float64(15.0),
                                   product_idx, pue_aware=pue_aware)

    assert fd_vs_ad(f_mu, 0.8) < REL_TOL
    assert fd_vs_ad(f_rho, 0.15) < REL_TOL


@pytest.mark.parametrize("pue_aware", AWARE)
@pytest.mark.parametrize("product_idx", PRODUCTS)
def test_throughput_score_grad(product_idx, pue_aware):
    del pue_aware  # throughput is meter-free; keep the full matrix anyway
    cw = jnp.float64(0.88)

    def f_mu(mu):
        return tier3.throughput_score(mu, jnp.float64(0.2), cw, product_idx,
                                      ckpt_cost_s=jnp.float64(30.0))

    def f_rho(rho):
        return tier3.throughput_score(jnp.float64(0.75), rho, cw,
                                      product_idx,
                                      ckpt_cost_s=jnp.float64(30.0))

    assert fd_vs_ad(f_mu, 0.75) < REL_TOL
    assert fd_vs_ad(f_rho, 0.2) < REL_TOL


def _ensemble64(n_ens: int = 8) -> bidding.BidEnsemble:
    return bidding.BidEnsemble(
        green=jnp.linspace(0.2, 0.9, n_ens).astype(jnp.float64),
        t_amb=jnp.linspace(5.0, 20.0, n_ens).astype(jnp.float64),
        price_rel=jnp.exp(jnp.linspace(-0.2, 0.2, n_ens)).astype(
            jnp.float64),
        epd=jnp.full((n_ens,), 4.0, jnp.float64))


W64 = np.asarray([tier3.W_FFR, tier3.W_CFE, tier3.W_REV_DEFAULT, 0.1],
                 np.float64)


def _ens_obj(mu, rho, bid, ens, product_idx, *, pue_aware, smooth):
    return bidding.ensemble_objective(
        mu, rho, bid, ens, W64, product_idx, jnp.float64(0.88),
        jnp.float64(30.0), pue_aware=pue_aware, use_workload=True,
        smooth=smooth)


@pytest.mark.parametrize("pue_aware", AWARE)
@pytest.mark.parametrize("product_idx", PRODUCTS)
@pytest.mark.parametrize("smooth", [True, False])
def test_ensemble_settlement_objective_grad(product_idx, pue_aware, smooth):
    """The full ensemble settlement objective -- what the optimiser
    actually differentiates (smooth) and ranks with (hard)."""
    with jax.enable_x64(True):
        ens = _ensemble64()
    point = {"mu": 0.75, "rho": 0.2, "bid": 0.18}
    for wrt, x0 in point.items():
        def f(v):
            p = dict(point)
            p = {k: jnp.float64(x) for k, x in p.items()}
            p[wrt] = v
            return _ens_obj(p["mu"], p["rho"], p["bid"], ens, product_idx,
                            pue_aware=pue_aware, smooth=smooth)
        assert fd_vs_ad(f, x0) < REL_TOL, (wrt, product_idx, pue_aware)


@pytest.mark.parametrize("side", [-0.02, 0.0, 0.02])
def test_no_nan_or_zero_grad_at_residual_load_boundary(side):
    """The smooth surrogate must keep a finite, NONZERO gradient at and
    around ``mu - rho == MIN_RESIDUAL_LOAD``: the hard objective's
    ``where`` gate zeroes the infeasible side (a plateau the optimiser
    could stall in), which is exactly what the sigmoid gate removes."""
    rho_b = 0.30
    mu_b = tier3.MIN_RESIDUAL_LOAD + rho_b + side
    with jax.enable_x64(True):
        ens = _ensemble64()

        def f_mu(mu):
            return _ens_obj(mu, jnp.float64(rho_b), jnp.float64(rho_b),
                            ens, 0, pue_aware=True, smooth=True)

        def f_rho(rho):
            return _ens_obj(jnp.float64(mu_b), rho, rho, ens, 0,
                            pue_aware=True, smooth=True)

        g_mu = float(jax.grad(f_mu)(jnp.float64(mu_b)))
        g_rho = float(jax.grad(f_rho)(jnp.float64(rho_b)))
    assert np.isfinite(g_mu) and np.isfinite(g_rho)
    assert abs(g_mu) > 1e-6 and abs(g_rho) > 1e-6


def test_float32_paths_unchanged():
    """The dtype relaxation that enables the f64 harness must leave the
    ordinary float32 graphs bit-identical: f32 in -> f32 out."""
    v = tier3.revenue_score(jnp.float32(0.8), jnp.float32(0.15),
                            jnp.float32(15.0), 0, pue_aware=True)
    q = tier3.q_ffr(0.7, 0.2, 15.0, pue_aware=True)
    t = tier3.throughput_score(0.75, 0.2, 0.88, 0)
    assert v.dtype == jnp.float32
    assert q.dtype == jnp.float32
    assert t.dtype == jnp.float32
