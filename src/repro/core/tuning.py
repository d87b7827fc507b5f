"""Tuning-parameter selection: the modified BIC of Zhang et al. (2016)
(paper Section 4.1), k-fold cross-validation, and the Theorem-3 bandwidth
rule.

    BIC(lambda) = N^-1 sum_l sum_i (1 - y_i x_i' b_l)_+
                  + sqrt(log N) * log p * mean_l |supp(b_l)| / N

(the paper's display omits the 1/N on the penalty; we normalize both terms
per-sample so the criterion is scale-consistent — noted in DESIGN.md).
A gossip protocol would broadcast the two scalars in deployment; here the
reduction is exact.

Ways to traverse the lambda grid:

- **cold** (``select_lambda``): host Python loop, each lambda refit from
  zero through ``decsvm_fit``.  Since ``ADMMConfig.lam`` is static under
  jit this recompiles per grid point — it is the reference semantics, and
  the baseline the path engine is benchmarked against
  (``benchmarks/bench_lambda_path.py``).  Always the slowest.
- **batched** (``repro.core.path.decsvm_path_batched``): one compile, all
  grid points advance in lockstep under ``vmap``.  Same trajectories as
  cold (zero start, fixed iteration count); best accelerator utilization
  at small scale, and the mode to use when the path must match the
  reference.
- **warm** (``repro.core.path.decsvm_path_warm``): one compile, sequential
  continuation over decreasing lambda with warm starts (A7) and per-lambda
  early stopping — by default on the KKT/duality-gap residual, which
  certifies solution quality but costs one extra network-gradient
  evaluation per round.  Fewest total ADMM rounds; whether that beats
  batched wall-clock depends on how aggressively the tolerance lets grid
  points stop (see ``BENCH_lambda_path.json`` for the current trade).
- **mesh** (``repro.core.decentral.decsvm_path_mesh``): the grid sharded
  over a true 2-D (node, lam) device mesh; grid points stop multiplying
  per-device memory and compute.

Selection criteria, both fused into the traversal's compiled program:
the modified BIC above (``criterion="bic"``) and k-fold cross-validated
held-out hinge loss (``criterion="cv"``, folds from ``kfold_masks``).

``select_lambda_path`` wraps the on-device engines with this module's
(best_lam, best_B, table) convention; ``select_lambda_path_many`` is the
problem-batched counterpart (a stack of same-shape problems through ONE
compiled program — the fit-serving bucket executor).
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Sequence

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import metrics, solver, trace


def modified_bic(X: np.ndarray, y: np.ndarray, B: np.ndarray,
                 tol: float = 1e-8) -> float:
    """X: (m, n, p), y: (m, n), B: (m, p).  NumPy reference."""
    X, y, B = map(np.asarray, (X, y, B))
    m, n, p = X.shape
    N = m * n
    margins = y * np.einsum("mnp,mp->mn", X, B)
    hinge = np.maximum(1.0 - margins, 0.0).sum() / N
    mean_supp = np.mean([(np.abs(b) > tol).sum() for b in B])
    return hinge + math.sqrt(math.log(N)) * math.log(p) * mean_supp / N


def modified_bic_jnp(X, y, B, tol: float = 1e-8):
    """jnp port of ``modified_bic`` — traceable, so the path engine can
    fuse scoring into the same compiled program as the fits."""
    m, n, p = X.shape
    N = m * n
    margins = y * jnp.einsum("mnp,mp->mn", X, B, precision=solver.F32)
    hinge = jnp.sum(jnp.maximum(1.0 - margins, 0.0)) / N
    mean_supp = jnp.mean(jnp.sum(jnp.abs(B) > tol, axis=1).astype(X.dtype))
    return hinge + math.sqrt(math.log(N)) * math.log(p) * mean_supp / N


def kfold_masks(m: int, n: int, k: int, seed: int = 0) -> np.ndarray:
    """(k, m, n) train masks in {0,1} for k-fold CV over each node's samples.

    Fold assignment is a per-node random permutation of ``range(n)`` taken
    mod k, so every fold holds out ~n/k samples *per node* (the network
    analogue of stratified folds: no node ever loses all its data, which
    would zero its local gradient).  mask==1 marks training rows; the
    validation rows of fold j are the complement.
    """
    if not 2 <= k <= n:
        raise ValueError(f"need 2 <= k <= n, got k={k}, n={n}")
    rng = np.random.default_rng(seed)
    fold_of = np.stack([rng.permutation(n) % k for _ in range(m)])  # (m, n)
    masks = np.ones((k, m, n), np.float32)
    for j in range(k):
        masks[j][fold_of == j] = 0.0
    return masks


def _lambda_max(X: np.ndarray, y: np.ndarray) -> float:
    """|X'y/N|_inf — the all-zero (hinge-subgradient) threshold."""
    X2 = np.asarray(X).reshape(-1, X.shape[-1])
    y2 = np.asarray(y).reshape(-1)
    return float(np.max(np.abs(X2.T @ y2)) / len(y2))


def _log_grid(lam_max: float, num: int, min_frac: float) -> np.ndarray:
    """The repo's one grid convention: log-spaced, *decreasing* from
    lam_max to lam_max * min_frac (the order warm continuation needs)."""
    return np.logspace(math.log10(lam_max), math.log10(lam_max * min_frac),
                       num)


def lambda_grid(X: np.ndarray, y: np.ndarray, num: int = 12,
                min_frac: float = 1e-3) -> np.ndarray:
    """Log-spaced grid below lambda_max = |X'y/N|_inf (all-zero threshold).

    Returned in *decreasing* order — the traversal order the warm-start
    continuation engine requires.
    """
    return _log_grid(_lambda_max(X, y), num, min_frac)


def select_lambda(fit_fn: Callable[[float], np.ndarray], X: np.ndarray,
                  y: np.ndarray, lams: Sequence[float]):
    """Cold-start reference loop: fit at each lambda on the host, return
    (best_lambda, best_B, table).  Prefer ``select_lambda_path`` for any
    grid larger than a few points — it compiles once instead of per-point.
    """
    best = (None, None, np.inf)
    table = []
    for lam in lams:
        B = np.asarray(fit_fn(float(lam)))
        crit = modified_bic(X, y, B)
        table.append((float(lam), crit, metrics.mean_support_size(B)))
        if crit < best[2]:
            best = (float(lam), B, crit)
    return best[0], best[1], table


def select_lambda_path(X, y, W, cfg, lams: Optional[Sequence[float]] = None,
                       num: int = 12, mode: str = "warm", tol: float = 1e-6,
                       lam_weights=None, criterion: str = "bic",
                       cv_folds: int = 5, cv_seed: int = 0,
                       stop_rule: str = "kkt", engine: str = "dense",
                       mesh=None, schedule: str = "gather",
                       check_every: int = 4):
    """On-device grid selection via ``repro.core.path`` / ``decentral``.

    Builds ``lambda_grid(X, y, num)`` when ``lams`` is omitted, runs the
    batched or warm-start traversal, scores it with the modified BIC
    (``criterion="bic"``) or k-fold cross-validation (``"cv"``), and
    returns the same (best_lam, best_B, table) triple as
    ``select_lambda`` — table rows are (lambda, criterion, mean support
    size).  The full on-device ``PathResult`` is returned as a fourth
    element.  ``engine="mesh"`` routes the traversal through the 2-D
    (node, lam) device-mesh engine (``decentral.decsvm_path_mesh``);
    ``engine="chunked"`` runs the same mesh engine in its block schedule
    (chunked node-megabatch layout: any m, m >> devices supported, and
    ``W`` may be a ``graph.BlockTopology``).

    ``check_every`` (dense engine, warm mode only): evaluate the stop
    statistic every k-th round instead of every round.  The mesh engine
    ignores it — its KKT residual contains mesh collectives that must
    run on every round, so it always checks per round.
    """
    from repro.core import path as path_mod  # local import: avoid cycle

    if lams is None:
        with TraceAnnotation(trace.SPAN_LAMBDA_GRID):
            lams = lambda_grid(np.asarray(X), np.asarray(y), num=num)
    with TraceAnnotation(trace.SPAN_PATH_PROGRAM):
        if engine in ("mesh", "chunked"):
            from repro.core import decentral  # local import: avoid cycle
            if engine == "chunked":
                schedule = "block"
            else:
                W = np.asarray(W)
            res = decentral.decsvm_path_mesh(
                jnp.asarray(X), jnp.asarray(y), W, lams, cfg,
                mesh=mesh, schedule=schedule, mode=mode, tol=tol,
                lam_weights=lam_weights, stop_rule=stop_rule,
                criterion=criterion, cv_folds=cv_folds, cv_seed=cv_seed)
        elif engine == "dense":
            res = path_mod.decsvm_path_select(
                jnp.asarray(X), jnp.asarray(y), jnp.asarray(W),
                jnp.asarray(lams), cfg, mode=mode, tol=tol,
                lam_weights=lam_weights, stop_rule=stop_rule,
                criterion=criterion, cv_folds=cv_folds, cv_seed=cv_seed,
                check_every=check_every)
        else:
            raise ValueError(
                f"engine {engine!r} not in ('dense', 'mesh', 'chunked')")
    with TraceAnnotation(trace.SPAN_BIC_TABLE):
        table = [(float(l), float(c),
                  metrics.mean_support_size(np.asarray(B)))
                 for l, c, B in zip(np.asarray(res.lams),
                                    np.asarray(res.criteria),
                                    np.asarray(res.path))]
        return float(res.best_lam), np.asarray(res.best_B), table, res


def shared_lambda_grid(Xs: np.ndarray, ys: np.ndarray, num: int = 12,
                       min_frac: float = 1e-3) -> np.ndarray:
    """One grid for a stack of problems: lambda_max is the max of the
    per-problem all-zero thresholds, so the grid's top point (nearly)
    zeroes every problem in the bucket.  Xs: (B, m, n, p), ys: (B, m, n);
    decreasing, same convention as ``lambda_grid``.
    """
    Xs, ys = np.asarray(Xs), np.asarray(ys)
    lam_max = max(_lambda_max(Xb, yb) for Xb, yb in zip(Xs, ys))
    return _log_grid(lam_max, num, min_frac)


def select_lambda_path_many(Xs, ys, Ws, cfg,
                            lams: Optional[Sequence[float]] = None,
                            num: int = 12, mode: str = "warm",
                            tol: float = 1e-6, lam_weights=None,
                            criterion: str = "bic", cv_folds: int = 5,
                            cv_seed: int = 0, stop_rule: str = "kkt",
                            check_every: int = 4):
    """Problem-batched ``select_lambda_path``: B same-shape problems, one
    compiled program (``repro.core.path.decsvm_path_select_many``).

    Xs: (B, m, n, p), ys: (B, m, n), Ws: (B, m, m).  All problems share
    one grid — ``lams`` explicitly, or ``shared_lambda_grid(num)`` (the
    per-problem ``lambda_grid`` would differ per dataset and break the
    single-program batching; pass explicit grids when parity with a
    specific serial grid matters).

    Returns (best_lams (B,), best_Bs (B, m, p), tables, res) where
    ``tables[b]`` is the per-problem (lambda, criterion, support) table
    and ``res`` the batched on-device ``PathResult``.
    """
    from repro.core import path as path_mod  # local import: avoid cycle

    Xs = np.asarray(Xs) if not hasattr(Xs, "dtype") else Xs
    if lams is None:
        lams = shared_lambda_grid(np.asarray(Xs), np.asarray(ys), num=num)
    res = path_mod.decsvm_path_select_many(
        jnp.asarray(Xs), jnp.asarray(ys), jnp.asarray(Ws), jnp.asarray(lams),
        cfg, mode=mode, tol=tol, lam_weights=lam_weights,
        stop_rule=stop_rule, criterion=criterion, cv_folds=cv_folds,
        cv_seed=cv_seed, check_every=check_every)
    lams_np = np.asarray(res.lams)          # (B, L)
    crits_np = np.asarray(res.criteria)     # (B, L)
    path_np = np.asarray(res.path)          # (B, L, m, p)
    tables = [[(float(l), float(c), metrics.mean_support_size(B))
               for l, c, B in zip(lams_np[b], crits_np[b], path_np[b])]
              for b in range(path_np.shape[0])]
    return (np.asarray(res.best_lam), np.asarray(res.best_B), tables, res)
