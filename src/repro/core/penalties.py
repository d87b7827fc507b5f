"""Folded-concave penalties via one-step local linear approximation (LLA).

Paper Section 2.3(iii) and Conclusion: the generalized ADMM extends to
SCAD (Fan & Li 2001), MCP (Zhang 2010) and the adaptive lasso (Zou 2006)
"via a straightforward linear approximation" (Zou & Li 2008).  The LLA
recipe: fit the l1 solution (stage 1), then re-fit with per-coordinate
penalty weights lam_j = pen'(|beta_j^(1)|; lam) / lam (stage 2).  The
per-coordinate weights multiply the soft-threshold level of the unified
Algorithm-1 step (``repro.core.solver``), so *every* engine — dense,
Pallas, node-chunked, 2-D mesh — runs the stage-2 solve unchanged
(``engine="sharded"`` routes both stages through
``repro.core.decentral``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admm import ADMMConfig, decsvm_fit

Array = jax.Array


def scad_weight(beta: Array, lam: float, a: float = 3.7) -> Array:
    """SCAD'(|b|)/lam: 1 on [0, lam], decays linearly, 0 beyond a*lam."""
    ab = jnp.abs(beta)
    w = jnp.where(ab <= lam, 1.0,
                  jnp.maximum(a * lam - ab, 0.0) / ((a - 1.0) * lam))
    return w


def mcp_weight(beta: Array, lam: float, gamma: float = 3.0) -> Array:
    """MCP'(|b|)/lam = max(0, 1 - |b|/(gamma*lam))."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(beta) / (gamma * lam))


def adaptive_weight(beta: Array, lam: float, eps: float = 0.05,
                    power: float = 1.0) -> Array:
    """Adaptive-lasso weights (eps/(|b|+eps))^power in (0, 1]."""
    return (eps / (jnp.abs(beta) + eps)) ** power


PENALTIES = {
    "scad": scad_weight,
    "mcp": mcp_weight,
    "adaptive": adaptive_weight,
}


def decsvm_fit_lla(X: Array, y: Array, W: Array, cfg: ADMMConfig,
                   penalty: str = "scad",
                   lams: Optional[Sequence[float]] = None,
                   path_mode: str = "warm", engine: str = "dense",
                   mesh=None, **pen_kwargs):
    """Two-stage LLA: l1 pilot -> penalty-weighted re-fit.

    When ``lams`` is given, the stage-1 pilot comes from the lambda-path
    engine — ``repro.core.path`` for ``engine="dense"``, the 2-D
    node_chunk x lambda mesh (``decentral.decsvm_path_mesh``) for
    ``engine="sharded"`` — the modified BIC picks lambda, and both the
    pilot and the stage-2 penalty level use the selected value: one
    compiled program instead of a per-lambda refit loop.  Otherwise the
    pilot is a single l1 fit at ``cfg.lam``.

    engine: "dense" (single-process) or "sharded" (node state chunked over
    the devices, ``decentral.decsvm_fit_chunked``; the stage-2
    per-coordinate ``lam_weights`` ride the sharded step unchanged).
    ``W`` may then be a ``graph.BlockTopology``.

    Weights are computed from the network-average pilot (each node can form
    it with one extra all-reduce round in deployment).
    Returns (B_stage2, weights).
    """
    if penalty not in PENALTIES:
        raise ValueError(f"penalty {penalty!r} not in {sorted(PENALTIES)}")
    if engine not in ("dense", "sharded"):
        raise ValueError(f"engine {engine!r} not in ('dense', 'sharded')")
    if lams is not None:
        if engine == "sharded":
            from repro.core import decentral  # local import: avoid cycle
            res = decentral.decsvm_path_mesh(
                X, y, W, np.asarray(lams), cfg, mesh=mesh, mode=path_mode)
        else:
            from repro.core import path as path_mod  # local: avoid cycle
            res = path_mod.decsvm_path_select(X, y, W, jnp.asarray(lams),
                                              cfg, mode=path_mode)
        cfg = dataclasses.replace(cfg, lam=float(res.best_lam))
        B1 = res.best_B
    elif engine == "sharded":
        from repro.core import decentral  # local import: avoid cycle
        B1 = decentral.decsvm_fit_chunked(X, y, W, cfg, mesh=mesh)
    else:
        B1 = decsvm_fit(X, y, W, cfg)
    pilot = jnp.mean(B1, axis=0)
    w = PENALTIES[penalty](pilot, cfg.lam, **pen_kwargs)
    if engine == "sharded":
        from repro.core import decentral  # local import: avoid cycle
        B2 = decentral.decsvm_fit_chunked(X, y, W, cfg, mesh=mesh,
                                          lam_weights=w)
    else:
        B2 = decsvm_fit(X, y, W, cfg, lam_weights=w)
    return B2, w
