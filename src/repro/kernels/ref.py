"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import losses, solver

Array = jax.Array


def decsvm_local_update(X: Array, y: Array, beta: Array, p_dual: Array,
                        neigh: Array, rho, omega, lam,
                        h: float, kernel: str = "epanechnikov") -> Array:
    """Oracle for the fused ADMM local update (paper eq. 7a') — the
    unified Algorithm-1 update of ``repro.core.solver``, verbatim (the
    Pallas kernel is validated against the exact math every driver runs).

    X: (n, p), y: (n,), beta/p_dual/neigh: (p,); rho/omega scalars; lam a
    scalar or (p,) per-coordinate penalty vector.
    neigh is the precomputed tau * sum_{k in N(l)} (beta_l + beta_k) term.
    Returns beta_new (p,).
    """
    return solver.local_update(X, y, beta, p_dual, neigh, rho, omega, lam,
                               h=h, kernel=kernel)


def decsvm_round_block(X: Array, y: Array, B: Array, P: Array, W: Array,
                       deg: Array, rho: Array, omega: Array, lam_vec,
                       nact: int, *, tau: float, lam0: float, h: float,
                       kernel: str = "epanechnikov",
                       want_kkt: bool = False):
    """Oracle for the round megakernel: ``nact`` dense Algorithm-1 rounds
    (each one exactly ``solver.local_update`` + the dense W@B neighbour
    sums) followed by the same stop statistic the kernel emits — the KKT
    residual of ``solver.kkt_residual`` when ``want_kkt``, else the last
    round's max|dB|.  Returns (B, P, stat), all fp32.
    """
    import types

    X = X.astype(jnp.float32)
    y = y.astype(jnp.float32)
    B, P = B.astype(jnp.float32), P.astype(jnp.float32)
    delta = jnp.asarray(jnp.inf, jnp.float32)
    for _ in range(int(nact)):
        neigh = tau * (deg[:, None] * B + W @ B)
        B_new = jax.vmap(
            lambda Xl, yl, bl, pl, nl, rl, wl: solver.local_update(
                Xl, yl, bl, pl, nl, rl, wl, lam_vec, h=h, kernel=kernel)
        )(X, y, B, P, neigh, rho, omega)
        P = P + tau * (deg[:, None] * B_new - W @ B_new)
        delta = jnp.max(jnp.abs(B_new - B))
        B = B_new
    if want_kkt:
        cfg = types.SimpleNamespace(kernel=kernel, h=h, lam0=lam0,
                                    backend="jnp")
        prob = solver.Problem(X, y, deg, rho, omega, None)
        lam_arr = jnp.asarray(lam_vec, jnp.float32).reshape(-1)
        if lam_arr.shape[0] == 1:
            stat = solver.kkt_residual(prob, cfg, B, lam_arr[0])
        else:
            stat = solver.kkt_residual(prob, cfg, B, 1.0, lam_arr)
        return B, P, stat
    return B, P, delta


def mha(q: Array, k: Array, v: Array, *, causal: bool = True,
        window: int | None = None, sm_scale: float | None = None) -> Array:
    """Grouped-query attention oracle.

    q: (B, H, S, D); k, v: (B, KV, S, D) with H % KV == 0.
    window: sliding-window width (attend to [i-window+1, i]); None = full.
    """
    B, H, S, D = q.shape
    KV = k.shape[1]
    g = H // KV
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(D).astype(q.dtype)
    kr = jnp.repeat(k, g, axis=1)
    vr = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vr.astype(jnp.float32))
    return out.astype(q.dtype)
