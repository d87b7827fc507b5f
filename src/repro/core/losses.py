"""Convolution-smoothed hinge losses (paper Section 2.2, Lemma 2.1).

The hinge loss L(u) = (1-u)_+ is convolved with a kernel K_h(u) = K(u/h)/h,
yielding L_h = L * K_h.  With z = (1 - v)/h every kernel admits closed forms:

    L_h (v) = (1-v) * F_K(z) - h * M_K(z)          (F_K = kernel CDF,
    L_h'(v) = -F_K(z)                               M_K(z) = int_-inf^z t K(t) dt)
    L_h''(v) = K(z) / h

All functions are elementwise, jnp-native, and autodiff-consistent
(``jax.grad`` of ``loss`` equals ``dloss`` — tested).  ``lipschitz(h)``
returns c_h of Lemma 2.1: the Lipschitz constant of L_h'.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.scipy.stats import norm as _norm

Array = jax.Array

KERNELS = ("laplacian", "logistic", "gaussian", "uniform", "epanechnikov")


def hinge(v: Array) -> Array:
    """The original (unsmoothed) hinge loss (1 - v)_+."""
    return jnp.maximum(1.0 - v, 0.0)


def hinge_subgrad(v: Array) -> Array:
    """A subgradient of the hinge loss (used by the D-subGD baseline)."""
    return jnp.where(v < 1.0, -1.0, 0.0)


# ---------------------------------------------------------------------------
# Closed-form smoothed losses.  Each entry defines loss / dloss / ddloss / c_h.
# ---------------------------------------------------------------------------

def _z(v: Array, h: float) -> Array:
    return (1.0 - v) / h


# -- Laplacian K(u) = exp(-|u|)/2 -------------------------------------------

@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _laplacian_loss(v, h):
    z = _z(v, h)
    return jnp.maximum(1.0 - v, 0.0) + 0.5 * h * jnp.exp(-jnp.abs(z))


def _laplacian_dloss(v, h):
    z = _z(v, h)
    # -F_K(z); F_K(z) = 0.5 e^z (z<0), 1 - 0.5 e^-z (z>=0)
    return -jnp.where(z < 0, 0.5 * jnp.exp(z), 1.0 - 0.5 * jnp.exp(-z))


@_laplacian_loss.defjvp
def _laplacian_loss_jvp(h, primals, tangents):
    # The value above sums two kinks at v=1 that cancel mathematically but
    # not under AD subgradient choices; route grad through the closed form.
    (v,), (dv,) = primals, tangents
    return _laplacian_loss(v, h), _laplacian_dloss(v, h) * dv


def _laplacian_ddloss(v, h):
    z = _z(v, h)
    return 0.5 * jnp.exp(-jnp.abs(z)) / h


# -- Logistic K(u) = e^-u / (1+e^-u)^2 --------------------------------------

def _logistic_loss(v, h):
    return h * jax.nn.softplus(_z(v, h))


def _logistic_dloss(v, h):
    return -jax.nn.sigmoid(_z(v, h))


def _logistic_ddloss(v, h):
    s = jax.nn.sigmoid(_z(v, h))
    return s * (1.0 - s) / h


# -- Gaussian ----------------------------------------------------------------

# Rational erf of XLA's fp32 lowering (odd numerator / even denominator in
# x^2, argument clamped to [-4, 4]; |error| < 5e-7 against jax.scipy).
# Pallas kernels on the TPU have no erf primitive, so dloss -- the one
# Gaussian term the fused kernels evaluate -- spells erf out in ops they
# lower, and every backend computes the same function.
_ERF_NUM = (-2.72614225801306e-10, 2.77068142495902e-08,
            -2.10102402082508e-06, -5.69250639462346e-05,
            -7.34990630326855e-04, -2.95459980854025e-03,
            -1.60960333262415e-02)
_ERF_DEN = (-1.45660718464996e-05, -2.13374055278905e-04,
            -1.68282697438203e-03, -7.37332916720468e-03,
            -1.42647390514189e-02)


def _horner(coeffs, x):
    acc = jnp.zeros_like(x)
    for c in coeffs:
        acc = acc * x + c
    return acc


def erf_rational(x: Array) -> Array:
    """erf(x) from ops every backend lowers (Pallas TPU has no erf)."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * _horner(_ERF_NUM, x2) / _horner(_ERF_DEN, x2)


def _gaussian_loss(v, h):
    z = _z(v, h)
    return (1.0 - v) * _norm.cdf(z) + h * _norm.pdf(z)


def _gaussian_dloss(v, h):
    return -0.5 * (1.0 + erf_rational(_z(v, h) * (1.0 / math.sqrt(2.0))))


def _gaussian_ddloss(v, h):
    return _norm.pdf(_z(v, h)) / h


# -- Uniform K(u) = I(|u|<=1)/2 ----------------------------------------------

def _uniform_loss(v, h):
    z = jnp.clip(_z(v, h), -1.0, 1.0)
    mid = 0.25 * h * (z + 1.0) ** 2
    return jnp.where(_z(v, h) > 1.0, 1.0 - v, mid)


def _uniform_dloss(v, h):
    z = jnp.clip(_z(v, h), -1.0, 1.0)
    return -0.5 * (z + 1.0)


def _uniform_ddloss(v, h):
    z = _z(v, h)
    return jnp.where(jnp.abs(z) <= 1.0, 0.5 / h, 0.0)


# -- Epanechnikov K(u) = 0.75 (1-u^2) on [-1,1] -------------------------------

def _epanechnikov_loss(v, h):
    z = jnp.clip(_z(v, h), -1.0, 1.0)
    mid = h * (3.0 + 8.0 * z + 6.0 * z**2 - z**4) / 16.0
    return jnp.where(_z(v, h) > 1.0, 1.0 - v, mid)


def _epanechnikov_dloss(v, h):
    z = jnp.clip(_z(v, h), -1.0, 1.0)
    return -(2.0 + 3.0 * z - z**3) / 4.0


def _epanechnikov_ddloss(v, h):
    z = _z(v, h)
    return jnp.where(jnp.abs(z) <= 1.0, 0.75 * (1.0 - z**2) / h, 0.0)


@dataclasses.dataclass(frozen=True)
class SmoothedHinge:
    """A convolution-smoothed hinge loss for a fixed kernel family."""

    name: str
    _loss: Callable
    _dloss: Callable
    _ddloss: Callable
    _ch: float  # c_h = _ch / h  (Lemma 2.1)

    def loss(self, v: Array, h: float) -> Array:
        return self._loss(v, h)

    def dloss(self, v: Array, h: float) -> Array:
        return self._dloss(v, h)

    def ddloss(self, v: Array, h: float) -> Array:
        return self._ddloss(v, h)

    def lipschitz(self, h: float) -> float:
        """Lipschitz constant c_h of L_h' (Lemma 2.1)."""
        return self._ch / h


_REGISTRY = {
    "laplacian": SmoothedHinge("laplacian", _laplacian_loss, _laplacian_dloss,
                               _laplacian_ddloss, 0.5),
    "logistic": SmoothedHinge("logistic", _logistic_loss, _logistic_dloss,
                              _logistic_ddloss, 0.25),
    "gaussian": SmoothedHinge("gaussian", _gaussian_loss, _gaussian_dloss,
                              _gaussian_ddloss, 1.0 / jnp.sqrt(2.0 * jnp.pi).item()),
    "uniform": SmoothedHinge("uniform", _uniform_loss, _uniform_dloss,
                             _uniform_ddloss, 0.5),
    "epanechnikov": SmoothedHinge("epanechnikov", _epanechnikov_loss,
                                  _epanechnikov_dloss, _epanechnikov_ddloss, 0.75),
}


def get_kernel(name: str) -> SmoothedHinge:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown kernel {name!r}; choose from {KERNELS}") from None


def smoothed_hinge_loss(v: Array, h: float, kernel: str = "epanechnikov") -> Array:
    return get_kernel(kernel).loss(v, h)


def smoothed_hinge_grad(v: Array, h: float, kernel: str = "epanechnikov") -> Array:
    return get_kernel(kernel).dloss(v, h)


def default_bandwidth(n_total: int, p: int) -> float:
    """Paper Section 4.1: h = max{(log p / N)^(1/4), 0.05}."""
    return max((math.log(max(p, 2)) / max(n_total, 2)) ** 0.25, 0.05)
