"""Batched regularization-path engine: Algorithm 1 over a whole lambda grid
on-device (paper Section 4.1 tuning, executed without host round-trips).

Every traversal below drives the unified step of ``repro.core.solver``
(the update math lives there, once); this module contributes the grid
orchestration and the fused selection criteria:

- ``decsvm_path_batched``: ``vmap`` the ADMM iteration over lambda.  All
  grid points advance in lockstep for ``cfg.max_iter`` rounds; per-lambda
  trajectories are bitwise the cold loop's (same zero start, same update),
  so this is the drop-in replacement when reproducibility against the
  sequential reference matters.
- ``decsvm_path_warm``: ``lax.scan`` over *decreasing* lambda, seeding each
  fit with the previous solution (assumption A7 admits any warm start) and
  early-stopping per grid point.  The default stop rule is the
  KKT/duality-gap residual of ``solver.kkt_residual`` — it measures actual
  optimality of the running iterate, so a warm-started fit stops at the
  same solution quality as a cold one (the legacy iterate-progress rule,
  which stops whenever the iterate crawls and let warm fits deviate from
  cold by the tolerance when ``max_iter`` was small, remains available as
  ``stop_rule="progress"``).
- ``decsvm_path_cv``: k-fold cross-validation fused with the traversal —
  every (fold, lambda) fit runs in the same compiled program via the solver
  core's masked-gradient backend, and the held-out hinge loss is scored
  on-device.

``decsvm_path_select`` fuses modified-BIC (``tuning.modified_bic_jnp``) or
cross-validation scoring into the same program and returns
``(best_lam, best_B, path, criteria)`` as device arrays.  The sharded
counterparts (node-sharded and true 2-D node x lambda meshes) live in
``repro.core.decentral``.

**Problem batching** (the serving axis, orthogonal to the node x lambda
mesh): ``decsvm_path_select_many`` stacks same-shape ``(X, y, W)``
problems on a leading batch axis and runs every fit, its BIC/CV scoring,
and the per-problem argmin in ONE compiled program — per-problem
``rho``/``omega`` fall out of ``vmap`` over ``solver.make_problem``.
``decsvm_fit_many`` is the matching single-fit fan-out with *traced*
per-problem ``(lam, lam_weights)`` (so LLA stage-2 re-fits across a
bucket of tuned problems never recompile).  ``serving.fit`` buckets its
request queue onto these entry points.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import sanitize, solver, trace
from repro.core.admm import ADMMConfig
from repro.core.tuning import modified_bic_jnp

Array = jax.Array


class PathResult(NamedTuple):
    best_lam: Array   # ()      grid point minimizing the criterion
    best_B: Array     # (m, p)  node estimates at best_lam
    lams: Array       # (L,)    the grid, as traversed
    path: Array       # (L, m, p) solutions at every grid point
    criteria: Array   # (L,)    selection criterion (modified BIC / CV hinge)
    iters: Array      # (L,)    ADMM rounds actually run per grid point


@functools.partial(jax.jit, static_argnames=("cfg",))
def decsvm_path_batched(X: Array, y: Array, W: Array, lams: Array,
                        cfg: ADMMConfig,
                        lam_weights: Optional[Array] = None) -> Array:
    """Fit every lambda in parallel (vmap), cold-started, fixed iterations.

    X: (m, n, p), y: (m, n), W: (m, m), lams: (L,).
    Returns the path B: (L, m, p).  cfg.lam is ignored.
    """
    sanitize.reject_unsupported(cfg, "decsvm_path_batched")
    prob = solver.make_problem(X, y, W, cfg)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
    lams = jnp.asarray(lams, X.dtype)

    def fit_one(lam):
        return solver.run_fixed(step, prob, lam, lam_weights,
                                num_iters=cfg.max_iter).B

    return jax.vmap(fit_one)(lams)


@functools.partial(jax.jit, static_argnames=("cfg", "stop_rule",
                                             "check_every"))
def decsvm_path_warm(X: Array, y: Array, W: Array, lams: Array,
                     cfg: ADMMConfig, tol: float = 1e-6,
                     lam_weights: Optional[Array] = None,
                     stop_rule: str = "kkt",
                     check_every: int = 4):
    """Sequential continuation over *decreasing* lambda with warm starts.

    Each grid point seeds B from the previous solution (duals restart at
    zero) and early-stops once the stop statistic <= tol: the
    KKT/duality-gap residual by default (``stop_rule="kkt"``), or the
    legacy iterate-progress rule max|B_t - B_{t-1}| (``"progress"``).
    ``check_every=k`` evaluates the statistic every k-th round only
    (the KKT rule costs a network gradient per evaluation; the loop
    still stops only on a measured residual <= tol).
    Returns (path (L, m, p), iters (L,)).  cfg.lam is ignored.
    """
    if stop_rule not in ("kkt", "progress"):
        raise ValueError(f"stop_rule {stop_rule!r} not in ('kkt', 'progress')")
    sanitize.reject_unsupported(cfg, "decsvm_path_warm")
    prob = solver.make_problem(X, y, W, cfg)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
    lams = jnp.asarray(lams, X.dtype)
    residual_fn = (solver.kkt_residual_fn(cfg) if stop_rule == "kkt"
                   else None)

    def outer(B_carry, lam):
        state = solver.init_state(prob, B0=B_carry)
        final = solver.run_tol(step, prob, lam, lam_weights,
                               max_iter=cfg.max_iter, tol=tol, state=state,
                               residual_fn=residual_fn,
                               check_every=check_every)
        return final.B, (final.B, final.t)

    m, _, p = X.shape
    B0 = jnp.zeros((m, p), X.dtype)
    _, (path, iters) = jax.lax.scan(outer, B0, lams)
    return path, iters


@jax.jit
def score_path(X: Array, y: Array, path: Array) -> Array:
    """Modified BIC at every path point, on-device.  path: (L, m, p)."""
    with jax.named_scope(trace.BIC):
        return jax.vmap(lambda B: modified_bic_jnp(X, y, B))(path)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decsvm_path_cv(X: Array, y: Array, W: Array, lams: Array,
                   cfg: ADMMConfig, masks: Array,
                   lam_weights: Optional[Array] = None) -> Array:
    """k-fold cross-validation scores fused with the path traversal.

    masks: (k, m, n) train masks in {0,1} (``tuning.kfold_masks``); fold j
    fits on mask rows and scores the held-out hinge loss on the complement.
    Every (fold, lambda) fit is cold-started lockstep (batched semantics)
    inside one compiled program.  Returns cv (L,): mean held-out hinge per
    grid point — lower is better.
    """
    sanitize.reject_unsupported(cfg, "decsvm_path_cv")
    lams = jnp.asarray(lams, X.dtype)
    step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)

    def fold_scores(mask):
        prob = solver.make_problem(X, y, W, cfg, mask=mask)

        def fit_one(lam):
            return solver.run_fixed(step, prob, lam, lam_weights,
                                    num_iters=cfg.max_iter).B

        path = jax.vmap(fit_one)(lams)                      # (L, m, p)
        val = 1.0 - mask                                    # held-out rows
        margins = jnp.einsum("mnp,lmp->lmn", X, path,
                             precision=solver.F32) * y[None]
        hinge = jnp.maximum(1.0 - margins, 0.0) * val[None]
        return jnp.sum(hinge, axis=(1, 2)) / jnp.maximum(jnp.sum(val), 1.0)

    return jnp.mean(jax.vmap(fold_scores)(masks), axis=0)   # (L,)


@functools.partial(jax.jit, static_argnames=("cfg", "mode", "stop_rule",
                                             "check_every"))
def _path_select(X, y, W, lams, cfg, mode, tol, lam_weights, stop_rule,
                 cv_masks, check_every=4):
    if mode == "batched":
        path = decsvm_path_batched(X, y, W, lams, cfg, lam_weights)
        iters = jnp.full((path.shape[0],), cfg.max_iter, jnp.int32)
    else:
        path, iters = decsvm_path_warm(X, y, W, lams, cfg, tol, lam_weights,
                                       stop_rule=stop_rule,
                                       check_every=check_every)
    if cv_masks is None:
        crits = score_path(X, y, path)
    else:
        crits = decsvm_path_cv(X, y, W, lams, cfg, cv_masks, lam_weights)
    i = jnp.argmin(crits)
    lams = jnp.asarray(lams, X.dtype)
    return PathResult(lams[i], path[i], lams, path, crits, iters)


def _validate_select(mode, stop_rule, criterion, cfg=None):
    if cfg is not None:
        sanitize.reject_unsupported(cfg, "decsvm_path_select")
    if mode not in ("warm", "batched"):
        raise ValueError(f"mode {mode!r} not in ('warm', 'batched')")
    if stop_rule not in ("kkt", "progress"):
        raise ValueError(f"stop_rule {stop_rule!r} not in ('kkt', 'progress')")
    if criterion not in ("bic", "cv"):
        raise ValueError(f"criterion {criterion!r} not in ('bic', 'cv')")


def _cv_masks_for(shape_m, shape_n, criterion, cv_folds, cv_seed, dtype):
    if criterion != "cv":
        return None
    from repro.core.tuning import kfold_masks  # local import: avoid cycle
    return jnp.asarray(kfold_masks(shape_m, shape_n, cv_folds, seed=cv_seed),
                       dtype)


def decsvm_path_select(X: Array, y: Array, W: Array,
                       lams: Array | Sequence[float], cfg: ADMMConfig,
                       mode: str = "warm", tol: float = 1e-6,
                       lam_weights: Optional[Array] = None,
                       stop_rule: str = "kkt",
                       criterion: str = "bic",
                       cv_folds: int = 5, cv_seed: int = 0,
                       check_every: int = 4) -> PathResult:
    """Traverse the grid and pick lambda, in one compiled program.

    mode: "warm" (continuation + early stop, fastest) or "batched"
    (cold-start lockstep, matches the sequential reference).
    criterion: "bic" (modified BIC of Zhang et al. 2016) or "cv" (k-fold
    held-out hinge, ``cv_folds`` folds).  The whole path, its criteria,
    and the argmin stay on device; nothing forces a host sync until the
    caller reads the result.
    """
    _validate_select(mode, stop_rule, criterion, cfg)
    cv_masks = _cv_masks_for(X.shape[0], X.shape[1], criterion, cv_folds,
                             cv_seed, X.dtype)
    return _path_select(X, y, W, jnp.asarray(lams), cfg, mode, tol,
                        lam_weights, stop_rule, cv_masks, check_every)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decsvm_fit_many(Xs: Array, ys: Array, Ws: Array, lams: Array,
                    cfg: ADMMConfig,
                    lam_weights: Optional[Array] = None) -> Array:
    """Fit a stack of same-shape problems, each at its own *traced* lambda.

    Xs: (B, m, n, p), ys: (B, m, n), Ws: (B, m, m), lams: (B,) per-problem
    l1 levels, lam_weights: optional (B, p) per-problem per-coordinate
    multipliers.  Per-problem rho/omega come from ``vmap`` over
    ``solver.make_problem``.  Because lambda is traced, a bucket of LLA
    stage-2 re-fits (every problem at its own selected lambda and weights)
    runs through ONE compiled program — the per-problem
    ``dataclasses.replace(cfg, lam=...)`` recompile of the serial path
    disappears.  Returns B: (B, m, p); cfg.lam is ignored.
    """
    sanitize.reject_unsupported(cfg, "decsvm_fit_many")
    lams = jnp.asarray(lams, Xs.dtype)

    def one(X, y, W, lam, w):
        prob = solver.make_problem(X, y, W, cfg)
        step = solver.make_step(cfg, lambda B: solver.mm(W, B), W=W)
        return solver.run_fixed(step, prob, lam, w,
                                num_iters=cfg.max_iter).B

    if lam_weights is None:
        return jax.vmap(lambda X, y, W, lam: one(X, y, W, lam, None))(
            Xs, ys, Ws, lams)
    return jax.vmap(one)(Xs, ys, Ws, lams, jnp.asarray(lam_weights, Xs.dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "mode", "stop_rule",
                                             "check_every"))
def _path_select_many(Xs, ys, Ws, lams, cfg, mode, tol, lam_weights,
                      stop_rule, cv_masks, check_every):
    def one(X, y, W):
        return _path_select(X, y, W, lams, cfg, mode, tol, lam_weights,
                            stop_rule, cv_masks, check_every)

    return jax.vmap(one)(Xs, ys, Ws)


def decsvm_path_select_many(Xs: Array, ys: Array, Ws: Array,
                            lams: Array | Sequence[float], cfg: ADMMConfig,
                            mode: str = "warm", tol: float = 1e-6,
                            lam_weights: Optional[Array] = None,
                            stop_rule: str = "kkt",
                            criterion: str = "bic",
                            cv_folds: int = 5, cv_seed: int = 0,
                            check_every: int = 4) -> PathResult:
    """Problem-batched ``decsvm_path_select``: one program, many problems.

    Xs: (B, m, n, p), ys: (B, m, n), Ws: (B, m, m) stack B same-shape
    problems on a leading batch axis; ``lams`` (L,) is the shared grid for
    the bucket.  Every per-problem fit (all L grid points, warm or
    batched), the BIC/CV scoring, and each problem's argmin run inside a
    single compiled program — ``vmap`` over ``_path_select`` batches the
    whole pipeline, including per-problem rho/omega from
    ``solver.make_problem`` and per-problem early stopping in warm mode
    (vmapped ``while_loop`` freezes converged problems, so results match
    the per-problem serial traversal exactly).  CV folds reuse one mask
    set across the bucket (same (m, n, cv_folds, cv_seed) => same masks
    as the serial path, preserving parity).

    Returns a ``PathResult`` whose fields carry a leading (B,) axis:
    best_lam (B,), best_B (B, m, p), lams (B, L), path (B, L, m, p),
    criteria (B, L), iters (B, L).
    """
    _validate_select(mode, stop_rule, criterion, cfg)
    Xs = jnp.asarray(Xs)
    if Xs.ndim != 4:
        raise ValueError(f"Xs must be (B, m, n, p), got shape {Xs.shape}")
    cv_masks = _cv_masks_for(Xs.shape[1], Xs.shape[2], criterion, cv_folds,
                             cv_seed, Xs.dtype)
    return _path_select_many(Xs, jnp.asarray(ys), jnp.asarray(Ws),
                             jnp.asarray(lams), cfg, mode, tol, lam_weights,
                             stop_rule, cv_masks, check_every)
