"""Dense single-process driver for the penalized convoluted SVM
(paper Algorithm 1, updates (7a') and (7b)).

The update math lives in ``repro.core.solver`` — one ``SolverState``
pytree and one traced-lambda step shared by every engine in the repo.
This module binds that step to the dense neighbour sum (``W @ B`` with
node states stacked into B (m, p) / P (m, p)) and keeps the historical
public surface: ``ADMMConfig``, ``admm_step``, ``decsvm_fit``,
``objective``, ``hard_threshold_final``.  ``repro.core.decentral`` binds
the same step to real collectives (multi-device shard_map engines);
``repro.core.path`` drives it over a whole lambda grid.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import losses, sanitize, solver
# Re-exported: historically defined here, canonical home is core.solver.
from repro.core.solver import (SolverState, compute_rho,  # noqa: F401
                               power_iteration_lmax, soft_threshold)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ADMMConfig:
    lam: float = 0.05          # l1 penalty
    lam0: float = 0.0          # l2 (elastic net) penalty; 0 => pure l1
    tau: float = 1.0           # ADMM penalty parameter
    h: float = 0.25            # smoothing bandwidth
    kernel: str = "epanechnikov"
    max_iter: int = 300
    rho_safety: float = 1.05   # multiply the c_h * lmax bound by this
    use_pallas: bool = False   # route the local update through the TPU kernel
    backend: str = "auto"      # "auto" (use_pallas decides) | "jnp" |
    #                            "pallas" | "megakernel" | "megakernel_bf16"
    sanitize: bool = False     # thread checkify E1-E7 term checks through the
    #                            step and localize the first non-finite value
    #                            (dense drivers only; see core.sanitize)


class ADMMState(NamedTuple):
    B: Array      # (m, p) primal node estimates
    P: Array      # (m, p) accumulated duals  p_l = sum_k (u_lk + v_lk)
    t: Array      # iteration counter


def admm_step(X: Array, y: Array, W: Array, deg: Array, rho: Array,
              state: ADMMState, cfg: ADMMConfig,
              lam_weights: Optional[Array] = None) -> ADMMState:
    """One round of Algorithm 1 across all m nodes (compat wrapper over
    ``solver.make_step`` with the dense ``W @ B`` neighbour sum).

    lam_weights: optional (p,) per-coordinate multiplier of the l1 level —
    the hook for adaptive/SCAD/MCP penalties via one-step LLA
    (repro.core.penalties).
    """
    omega = 1.0 / (2.0 * cfg.tau * deg + rho + cfg.lam0)
    prob = solver.Problem(X.astype(solver.problem_dtype(cfg)), y, deg, rho,
                          omega, None)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
    st = solver.SolverState(state.B, state.P, state.t,
                            jnp.asarray(jnp.inf, jnp.float32))
    new = step(prob, st, cfg.lam, lam_weights)
    return ADMMState(new.B, new.P, new.t)


def _decsvm_fit_impl(X, y, W, beta0, lam_weights, cfg, track_history):
    prob = solver.make_problem(X, y, W, cfg)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
    state = solver.init_state(prob, B0=beta0)
    out = solver.run_fixed(step, prob, cfg.lam, lam_weights,
                           num_iters=cfg.max_iter, state=state,
                           track_history=track_history)
    if track_history:
        final, hist = out
        return final.B, hist
    return out.B


@functools.partial(jax.jit, static_argnames=("cfg", "track_history"))
def _decsvm_fit_jit(X, y, W, cfg, beta0=None, track_history=False,
                    lam_weights=None):
    return _decsvm_fit_impl(X, y, W, beta0, lam_weights, cfg, track_history)


def decsvm_fit(X: Array, y: Array, W: Array, cfg: ADMMConfig,
               beta0: Optional[Array] = None,
               track_history: bool = False,
               lam_weights: Optional[Array] = None):
    """Run Algorithm 1 for cfg.max_iter rounds.

    Args:
      X: (m, n, p) node-partitioned design (intercept included as a column).
      y: (m, n) labels in {-1, +1}.
      W: (m, m) adjacency.
      beta0: optional (m, p) warm start (A7 allows zeros).
      lam_weights: optional (p,) per-coordinate l1 multipliers (LLA stage 2).
    Returns:
      B: (m, p) final node estimates; and, if track_history, H: (T, m, p).

    With ``cfg.sanitize`` the same program runs under ``checkify`` and
    raises with the E1-E7 term + round localization of the first
    non-finite value (``core.sanitize``); without it, the traced program
    is bit-identical to a config predating the flag.
    """
    if sanitize.wants_sanitize(cfg):
        err, out = sanitize.checked_call(_decsvm_fit_impl, cfg,
                                         track_history)(
            X, y, W, beta0, lam_weights)
        err.throw()
        return out
    return _decsvm_fit_jit(X, y, W, cfg, beta0=beta0,
                           track_history=track_history,
                           lam_weights=lam_weights)


def objective(X: Array, y: Array, beta: Array, cfg: ADMMConfig) -> Array:
    """Network-wide smoothed elastic-net objective (eq. 3/4) at a common beta."""
    k = losses.get_kernel(cfg.kernel)
    margins = y * jnp.einsum("mnp,p->mn", X, beta, precision=solver.F32)
    data = jnp.mean(k.loss(margins, cfg.h))
    return data + 0.5 * cfg.lam0 * jnp.sum(beta**2) + cfg.lam * jnp.sum(jnp.abs(beta))


def hard_threshold_final(B: Array, lam: float) -> Array:
    """Theorem 4 post-processing: keep coordinates with |beta_j| > lambda.

    True *hard* thresholding — surviving coordinates are passed through
    unshrunk (soft-thresholding here would bias every survivor toward zero
    by lambda and inflate estimation error; the ADMM update itself is the
    only place soft-thresholding belongs).
    """
    return B * (jnp.abs(B) > lam)
