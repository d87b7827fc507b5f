"""Baseline estimators from the paper's Section 4 comparison:

  - Pooled  : l1/elastic-net penalized CSVM on ALL data (FISTA) — benchmark.
  - Local   : each node solves its own penalized CSVM on local data only.
  - Average : local estimates combined by average consensus (Yadav-Salapaka).
  - D-subGD : decentralized subgradient descent on the ORIGINAL (nonsmooth)
              hinge objective with Metropolis mixing — the slow competitor.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import losses
from repro.core.admm import ADMMConfig, power_iteration_lmax, soft_threshold
from repro.core.graph import metropolis_weights
from repro.core.solver import mm

Array = jax.Array


# ---------------------------------------------------------------------------
# Pooled CSVM: FISTA on smoothed loss + l2, prox on l1.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "max_iter"))
def pooled_csvm(X: Array, y: Array, cfg: ADMMConfig, max_iter: int = 500) -> Array:
    """FISTA for  (1/N) sum L_h(y x'b) + lam0/2 |b|^2 + lam |b|_1.

    X: (N, p) pooled design, y: (N,).
    """
    kern = losses.get_kernel(cfg.kernel)
    N = X.shape[0]
    L = kern.lipschitz(cfg.h) * power_iteration_lmax(X) + cfg.lam0
    step = 1.0 / (L * 1.01)

    def smooth_grad(b):
        margin = y * mm(X, b)
        return mm(X.T, kern.dloss(margin, cfg.h) * y) / N + cfg.lam0 * b

    def body(carry, _):
        b, z, tk = carry
        b_new = soft_threshold(z - step * smooth_grad(z), step * cfg.lam)
        tk_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z_new = b_new + (tk - 1.0) / tk_new * (b_new - b)
        return (b_new, z_new, tk_new), None

    b0 = jnp.zeros((X.shape[1],), X.dtype)
    (b, _, _), _ = jax.lax.scan(body, (b0, b0, jnp.ones(())), None, length=max_iter)
    return b


def local_csvm(X: Array, y: Array, cfg: ADMMConfig, max_iter: int = 500) -> Array:
    """Per-node pooled solve.  X: (m, n, p), y: (m, n) -> (m, p)."""
    return jax.vmap(lambda Xi, yi: pooled_csvm(Xi, yi, cfg, max_iter))(X, y)


def average_consensus(B_local: Array, W: np.ndarray, rounds: int = 100) -> Array:
    """Metropolis-weight gossip averaging of local estimates -> (m, p)."""
    M = jnp.asarray(metropolis_weights(np.asarray(W)))

    def body(B, _):
        return mm(M, B), None

    B, _ = jax.lax.scan(body, B_local, None, length=rounds)
    return B


@functools.partial(jax.jit, static_argnames=("lam", "max_iter", "lr0"))
def d_subgd(X: Array, y: Array, Wmix: Array, lam: float = 0.05,
            max_iter: int = 100, lr0: float = 0.05) -> Array:
    """Decentralized subgradient descent on the nonsmooth l1-hinge objective.

    b_l <- sum_k M_lk b_k - eta_t * ( (1/n) sum_i dL(y x'b) y x + lam sign(b) )
    with eta_t = lr0 / sqrt(t+1).  X: (m, n, p).
    """
    m, n, p = X.shape

    def node_subgrad(Xl, yl, bl):
        margin = yl * mm(Xl, bl)
        g = mm(Xl.T, losses.hinge_subgrad(margin) * yl) / n
        return g + lam * jnp.sign(bl)

    def body(B, t):
        mixed = mm(Wmix, B)
        G = jax.vmap(node_subgrad)(X, y, mixed)
        eta = lr0 / jnp.sqrt(t + 1.0)
        return mixed - eta * G, None

    B0 = jnp.zeros((m, p), X.dtype)
    B, _ = jax.lax.scan(body, B0, jnp.arange(max_iter, dtype=X.dtype))
    return B


def d_subgd_fit(X: Array, y: Array, W: np.ndarray, lam: float = 0.05,
                max_iter: int = 100, lr0: float = 0.05) -> Array:
    return d_subgd(X, y, jnp.asarray(metropolis_weights(np.asarray(W))),
                   lam=lam, max_iter=max_iter, lr0=lr0)
