"""Smoothed-loss properties (paper Section 2.2 + Lemma 2.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import losses

KERNELS = losses.KERNELS
HS = [0.05, 0.1, 0.25, 0.5, 1.0]


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("h", [0.1, 0.5])
def test_autodiff_matches_closed_form(kernel, h):
    kern = losses.get_kernel(kernel)
    v = jnp.linspace(-4, 4, 201)
    g_auto = jax.vmap(jax.grad(lambda u: kern.loss(u, h)))(v)
    np.testing.assert_allclose(g_auto, kern.dloss(v, h), atol=2e-5)
    h_auto = jax.vmap(jax.grad(jax.grad(lambda u: kern.loss(u, h))))(v)
    # second derivative may disagree exactly at kink boundaries for
    # compactly-supported kernels; compare away from |z|=1
    z = (1 - v) / h
    mask = jnp.abs(jnp.abs(z) - 1.0) > 1e-3
    np.testing.assert_allclose(np.where(mask, h_auto, 0),
                               np.where(mask, kern.ddloss(v, h), 0), atol=2e-4)


@pytest.mark.parametrize("kernel", KERNELS)
def test_convexity_and_monotonicity(kernel):
    kern = losses.get_kernel(kernel)
    v = jnp.linspace(-6, 6, 400)
    for h in HS:
        d = kern.dloss(v, h)
        assert bool(jnp.all(jnp.diff(d) >= -1e-6)), "L_h' must be nondecreasing"
        assert bool(jnp.all(d <= 1e-6)) and bool(jnp.all(d >= -1.0 - 1e-6)), \
            "-1 <= L_h' <= 0"
        assert bool(jnp.all(kern.ddloss(v, h) >= -1e-9))


@pytest.mark.parametrize("kernel", KERNELS)
def test_smoothing_bias_vanishes(kernel):
    """|L_h - L|_inf -> 0 as h -> 0 (Theorem 2 at the loss level)."""
    kern = losses.get_kernel(kernel)
    v = jnp.linspace(-4, 4, 301)
    prev = None
    for h in [0.5, 0.25, 0.1, 0.05, 0.01]:
        gap = float(jnp.max(jnp.abs(kern.loss(v, h) - losses.hinge(v))))
        assert gap <= h  # |L_h - L| <= c*h for bounded-support/variance K
        if prev is not None:
            assert gap <= prev + 1e-9
        prev = gap


@pytest.mark.parametrize("kernel", KERNELS)
def test_lipschitz_constant_lemma21(kernel):
    """Empirical Lipschitz constant of L_h' matches Lemma 2.1 (and is tight
    within 2% for the kernels with closed-form constants)."""
    kern = losses.get_kernel(kernel)
    for h in [0.1, 0.5]:
        v = jnp.linspace(-3, 3, 20001)
        d = kern.dloss(v, h)
        emp = float(jnp.max(jnp.abs(jnp.diff(d) / jnp.diff(v))))
        c_h = kern.lipschitz(h)
        assert emp <= c_h * 1.01, (emp, c_h)
        assert emp >= 0.8 * c_h, "claimed constant should be near-tight"


@settings(max_examples=50, deadline=None)
@given(v1=st.floats(-10, 10), v2=st.floats(-10, 10),
       h=st.sampled_from(HS),
       kernel=st.sampled_from(list(KERNELS)))
def test_quadratic_majorization(v1, v2, h, kernel):
    """Lemma 2.1: L_h(u) <= L_h(w) + L_h'(w)(u-w) + c_h (u-w)^2 / 2."""
    kern = losses.get_kernel(kernel)
    lhs = float(kern.loss(jnp.float32(v1), h))
    rhs = float(kern.loss(jnp.float32(v2), h)
                + kern.dloss(jnp.float32(v2), h) * (v1 - v2)
                + 0.5 * kern.lipschitz(h) * (v1 - v2) ** 2)
    assert lhs <= rhs + 1e-4 * max(1.0, abs(rhs))


@settings(max_examples=50, deadline=None)
@given(v=st.floats(-10, 10), h=st.sampled_from(HS),
       kernel=st.sampled_from(list(KERNELS)))
def test_loss_dominates_hinge_from_above_nonneg(v, h, kernel):
    """L_h >= 0 and L_h(v) >= L(v) for symmetric kernels (Jensen)."""
    kern = losses.get_kernel(kernel)
    lv = float(kern.loss(jnp.float32(v), h))
    assert lv >= -1e-6
    assert lv >= float(losses.hinge(jnp.float32(v))) - 1e-5


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("h", [0.1, 0.5])
def test_jax_hessian_matches_closed_form_curvature(kernel, h):
    """``jax.hessian`` of the node objective mean L_h(y Xb) equals the
    closed form X^T diag(L_h'' y^2) X / n — the curvature identity the
    rho bound (``solver.compute_rho`` via Lemma 2.1) relies on.  The
    evaluation point is verified away from the kernels' kink sets
    (|z| = 1 for compact support, z = 0 for the laplacian, whose loss
    routes grad through a custom_jvp) so every family is twice
    differentiable there."""
    kern = losses.get_kernel(kernel)
    rng = np.random.default_rng(3)
    n, p = 24, 5
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    y = jnp.asarray(np.sign(rng.normal(size=n) + 0.2), jnp.float32)
    beta = jnp.asarray(rng.normal(size=p) * 0.3, jnp.float32)

    margins = y * (X @ beta)
    z = (1 - margins) / h
    assert float(jnp.min(jnp.abs(jnp.abs(z) - 1.0))) > 1e-3
    assert float(jnp.min(jnp.abs(z))) > 1e-3

    def obj(b):
        return jnp.mean(kern.loss(y * (X @ b), h))

    H_auto = jax.hessian(obj)(beta)
    w = kern.ddloss(margins, h) * y**2
    H_closed = (X.T * w) @ X / n
    np.testing.assert_allclose(np.asarray(H_auto), np.asarray(H_closed),
                               rtol=1e-4, atol=1e-4)

    # and the rho bound really does dominate the curvature at this point
    lmax_H = float(jnp.max(jnp.linalg.eigvalsh(H_auto)))
    lmax_X = float(jnp.max(jnp.linalg.eigvalsh(X.T @ X / n)))
    assert lmax_H <= kern.lipschitz(h) * lmax_X * (1 + 1e-4)


def test_default_bandwidth_rule():
    h = losses.default_bandwidth(2000, 100)
    assert abs(h - max((np.log(100) / 2000) ** 0.25, 0.05)) < 1e-12
    assert losses.default_bandwidth(10**9, 10) == 0.05


def test_rational_erf_matches_jax_scipy():
    """The Gaussian dloss spells erf out in ops Pallas TPU lowers; it must
    stay within fp32 rounding of jax.scipy's erf, tails included."""
    from jax.scipy.special import erf
    x = jnp.linspace(-9.0, 9.0, 20001, dtype=jnp.float32)
    err = np.max(np.abs(np.asarray(losses.erf_rational(x))
                        - np.asarray(erf(x))))
    assert err < 1e-6, err
    v = jnp.linspace(-4.0, 6.0, 2001)
    from jax.scipy.stats import norm
    np.testing.assert_allclose(
        losses.get_kernel("gaussian").dloss(v, 0.25),
        -norm.cdf((1.0 - v) / 0.25), atol=1e-6)
