"""Batched lambda-path engine vs the cold-start reference loop."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ADMMConfig, SimConfig, decsvm_fit, generate, tuning
from repro.core import decentral
from repro.core.graph import erdos_renyi
from repro.core.path import (decsvm_path_batched, decsvm_path_select,
                             decsvm_path_warm)
from repro.core.penalties import decsvm_fit_lla

MAX_ITER = 150


@pytest.fixture(scope="module")
def sim():
    cfg = SimConfig(p=24, s=4, m=4, n=80, rho=0.5, mu=0.5)
    X, y, bstar = generate(cfg, seed=3)
    W = erdos_renyi(cfg.m, 0.7, seed=1)
    lams = tuning.lambda_grid(X, y, num=5)
    return (cfg, jnp.asarray(X), jnp.asarray(y),
            jnp.asarray(W, jnp.float32), lams)


@pytest.fixture(scope="module")
def cold_path(sim):
    cfg, X, y, W, lams = sim
    return np.stack([
        np.asarray(decsvm_fit(X, y, W, ADMMConfig(lam=float(l),
                                                  max_iter=MAX_ITER)))
        for l in lams])


def test_batched_matches_cold_loop_at_every_grid_point(sim, cold_path):
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    path = np.asarray(decsvm_path_batched(X, y, W, jnp.asarray(lams), acfg))
    assert path.shape == cold_path.shape
    np.testing.assert_allclose(path, cold_path, atol=1e-4)


def test_warm_start_selects_same_lambda_as_cold_select(sim):
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)

    def fit(lam):
        return decsvm_fit(X, y, W, ADMMConfig(lam=lam, max_iter=MAX_ITER))

    best_cold, B_cold, table = tuning.select_lambda(
        fit, np.asarray(X), np.asarray(y), lams)
    res = decsvm_path_select(X, y, W, jnp.asarray(lams), acfg, mode="warm",
                             tol=1e-7)
    assert float(res.best_lam) == pytest.approx(best_cold, rel=1e-5)
    # batched mode has cold semantics: its criteria match the host table
    res_b = decsvm_path_select(X, y, W, jnp.asarray(lams), acfg,
                               mode="batched")
    np.testing.assert_allclose(np.asarray(res_b.criteria),
                               [row[1] for row in table], atol=1e-3)
    assert float(res_b.best_lam) == pytest.approx(best_cold, rel=1e-5)


def test_warm_start_early_stops(sim):
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    _, iters = decsvm_path_warm(X, y, W, jnp.asarray(lams), acfg, tol=1e-4,
                                check_every=1)
    iters = np.asarray(iters)
    assert np.all(iters <= MAX_ITER)
    # at lambda_max the solution is all-zero: convergence is immediate
    assert iters[0] < MAX_ITER
    # sparse checking (default check_every=4) stops only on rounds it
    # actually measured; with tol above the residual's oscillation floor
    # it still stops early, on a multiple of the check interval
    _, iters4 = decsvm_path_warm(X, y, W, jnp.asarray(lams), acfg, tol=1e-3)
    iters4 = np.asarray(iters4)
    assert iters4[0] < MAX_ITER and iters4[0] % 4 == 0


def test_modified_bic_jnp_matches_numpy(sim, cold_path):
    cfg, X, y, W, lams = sim
    for B in cold_path:
        want = tuning.modified_bic(np.asarray(X), np.asarray(y), B)
        got = float(tuning.modified_bic_jnp(X, y, jnp.asarray(B)))
        assert got == pytest.approx(want, abs=1e-4)


def test_select_lambda_path_wrapper(sim):
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    best_lam, best_B, table, res = tuning.select_lambda_path(
        X, y, W, acfg, lams=lams, mode="batched")
    assert best_B.shape == (cfg.m, cfg.p + 1)
    assert len(table) == len(lams)
    crits = np.asarray(res.criteria)
    assert best_lam == pytest.approx(float(lams[int(np.argmin(crits))]))
    # BIC should not pick the densest (smallest-lambda) model
    assert best_lam > lams[-1]


def test_sharded_path_matches_batched(sim):
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    bat = np.asarray(decsvm_path_batched(X, y, W, jnp.asarray(lams), acfg))
    shd = np.asarray(decentral.decsvm_path_chunked(
        X, y, np.asarray(W), lams, acfg))
    np.testing.assert_allclose(shd, bat, atol=1e-5)


def test_cv_selection_alongside_bic(sim):
    """criterion="cv" scores the path with fused k-fold CV; both criteria
    run in one compiled program and pick a non-degenerate lambda."""
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    res_cv = decsvm_path_select(X, y, W, jnp.asarray(lams), acfg,
                                mode="batched", criterion="cv", cv_folds=3)
    res_bic = decsvm_path_select(X, y, W, jnp.asarray(lams), acfg,
                                 mode="batched", criterion="bic")
    assert res_cv.criteria.shape == (len(lams),)
    assert np.all(np.isfinite(np.asarray(res_cv.criteria)))
    # CV scores are held-out hinge: different scale from BIC
    assert not np.allclose(np.asarray(res_cv.criteria),
                           np.asarray(res_bic.criteria))
    # the full-data path is criterion-independent
    np.testing.assert_allclose(np.asarray(res_cv.path),
                               np.asarray(res_bic.path), atol=1e-6)
    # CV must not pick the all-zero (lambda_max) model
    assert float(res_cv.best_lam) < float(lams[0])


def test_mesh_engine_via_select_lambda_path(sim):
    """engine="mesh" routes selection through the 2-D (node_chunk, lam)
    mesh and agrees with the dense engine on path and criteria."""
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    best_d, B_d, table_d, res_d = tuning.select_lambda_path(
        X, y, W, acfg, lams=lams, mode="batched")
    best_m, B_m, table_m, res_m = tuning.select_lambda_path(
        X, y, W, acfg, lams=lams, mode="batched", engine="mesh")
    assert best_m == pytest.approx(best_d, rel=1e-5)
    np.testing.assert_allclose(B_m, B_d, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res_m.criteria),
                               np.asarray(res_d.criteria), atol=1e-4)


def test_lla_stage2_runs_sharded(sim):
    """The sharded engines accept lam_weights, so LLA stage 2 rides them
    (PR 3's per-coordinate fix reached dense+Pallas but not sharded)."""
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.06, max_iter=MAX_ITER)
    B_dense, w_dense = decsvm_fit_lla(X, y, W, acfg, penalty="scad")
    B_shard, w_shard = decsvm_fit_lla(X, y, W, acfg, penalty="scad",
                                      engine="sharded")
    np.testing.assert_allclose(np.asarray(w_shard), np.asarray(w_dense),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(B_shard), np.asarray(B_dense),
                               atol=1e-5)


def test_lla_stage1_pilot_from_path(sim):
    cfg, X, y, W, lams = sim
    acfg = ADMMConfig(lam=0.0, max_iter=MAX_ITER)
    B2, w = decsvm_fit_lla(X, y, W, acfg, penalty="scad", lams=lams)
    assert B2.shape == (cfg.m, cfg.p + 1)
    assert w.shape == (cfg.p + 1,)
    assert float(jnp.min(w)) >= 0.0 and float(jnp.max(w)) <= 1.0 + 1e-6
