"""Production conveniences on top of Algorithm 1 — thin drivers over the
unified step in ``repro.core.solver``:

- ``decsvm_fit_tol``: while-loop driver with early stopping — either the
  iterate-progress rule (progress = |B_t - B_{t-1}|) or the KKT/duality-gap
  rule of ``solver.kkt_residual`` (``stop_rule="kkt"``).
- ``decsvm_fit_uneven``: uneven local sample sizes n_l via sample masks
  (the paper's "straightforward extension" — Section 2.1); the masks ride
  the solver core's masked-gradient backend, the same machinery the k-fold
  cross-validation path engine uses.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax

from repro.core import sanitize, solver
from repro.core.admm import ADMMConfig

Array = jax.Array


def _fit_tol_impl(X, y, W, tol, cfg, stop_rule, check_every):
    prob = solver.make_problem(X, y, W, cfg)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
    residual_fn = (solver.kkt_residual_fn(cfg) if stop_rule == "kkt"
                   else None)
    final = solver.run_tol(step, prob, cfg.lam, max_iter=cfg.max_iter,
                           tol=tol, residual_fn=residual_fn,
                           check_every=check_every)
    return final.B, final.t


@functools.partial(jax.jit, static_argnames=("cfg", "stop_rule",
                                             "check_every"))
def _fit_tol_jit(X, y, W, cfg, tol=1e-6, stop_rule="progress",
                 check_every=4):
    return _fit_tol_impl(X, y, W, tol, cfg, stop_rule, check_every)


def decsvm_fit_tol(X: Array, y: Array, W: Array, cfg: ADMMConfig,
                   tol: float = 1e-6,
                   stop_rule: str = "progress",
                   check_every: int = 4) -> Tuple[Array, Array]:
    """Run Algorithm 1 until max_iter OR stop statistic < tol.

    stop_rule: "progress" (max|B_t - B_{t-1}|, the legacy rule) or "kkt"
    (stationarity + consensus residual of ``solver.kkt_residual`` — an
    actual optimality measure).  Returns (B, t).

    ``check_every`` evaluates the stop statistic only every k-th round
    (default 4): the KKT residual costs a full network-gradient per
    evaluation, so checking sparsely removes that per-round overhead
    while stopping at the same certified quality (the loop only ever
    stops on a residual it actually measured).  This is also the KKT
    exposure for the single-fit Pallas path: the fused kernel returns
    only B_new, so the residual is recomputed outside the fused update —
    every k rounds instead of every round.
    """
    if stop_rule not in ("kkt", "progress"):
        raise ValueError(f"stop_rule {stop_rule!r} not in ('kkt', 'progress')")
    if sanitize.wants_sanitize(cfg):
        err, out = sanitize.checked_call(_fit_tol_impl, cfg, stop_rule,
                                         check_every)(X, y, W, tol)
        err.throw()
        return out
    return _fit_tol_jit(X, y, W, cfg, tol=tol, stop_rule=stop_rule,
                        check_every=check_every)


def _fit_uneven_impl(X, y, mask, W, cfg):
    prob = solver.make_problem(X, y, W, cfg, mask=mask)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
    final = solver.run_fixed(step, prob, cfg.lam, num_iters=cfg.max_iter)
    return final.B


@functools.partial(jax.jit, static_argnames=("cfg",))
def _fit_uneven_jit(X, y, mask, W, cfg):
    return _fit_uneven_impl(X, y, mask, W, cfg)


def decsvm_fit_uneven(X: Array, y: Array, mask: Array, W: Array,
                      cfg: ADMMConfig) -> Array:
    """Algorithm 1 with per-node sample masks.

    X: (m, n_max, p) zero-padded designs; mask: (m, n_max) in {0,1} marking
    real rows (n_l = mask[l].sum()).  Updates are identical to (7a')/(7b)
    with n replaced by n_l per node — the solver core's masked-gradient
    backend; rho comes from the masked second moment (zero rows contribute
    nothing).
    """
    if sanitize.wants_sanitize(cfg):
        err, out = sanitize.checked_call(_fit_uneven_impl, cfg)(
            X, y, mask, W)
        err.throw()
        return out
    return _fit_uneven_jit(X, y, mask, W, cfg)
