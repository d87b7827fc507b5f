"""Gossip primitives for decentralized scalar aggregation.

Paper Section 4.1: "the use of a gossip protocol allows for efficient
broadcasting of scalar values (loss and estimated sparsity) across the
network" — used to evaluate the modified BIC without a fusion center.
Metropolis-weight gossip converges geometrically to the network average at
rate |lambda_2(M)| (Yadav & Salapaka 2007).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import metropolis_weights
from repro.core.solver import F32, mm

Array = jax.Array


def metropolis_weights_jnp(W: Array) -> Array:
    """Traced Metropolis–Hastings mixing matrix: M_ij = W_ij /
    (1 + max(deg_i, deg_j)) off-diagonal, rows summing to 1.  Matches
    ``graph.metropolis_weights`` (the host double loop) exactly but is
    jit/vmap-composable — no NumPy, no O(m^2) host work per call."""
    W = jnp.asarray(W)
    deg = jnp.sum(W, axis=1)
    pair_deg = jnp.maximum(deg[:, None], deg[None, :])
    M = W / (1.0 + pair_deg)
    diag = 1.0 - jnp.sum(M, axis=1)
    return M + jnp.diag(diag)


def gossip_average(values: Array, W: Array, rounds: int = 50) -> Array:
    """values: (m, ...) per-node scalars/vectors -> per-node estimates of the
    network average after `rounds` one-hop gossip exchanges.

    Fully traceable: ``W`` may be a device array (the mixing weights are
    computed with jnp ops, not the host loop in ``graph.metropolis_weights``)
    and the exchange itself is a ``lax.scan``, so the whole thing composes
    under jit/vmap and with the chunked engines.  ``rounds`` stays static
    (it sizes the scan).
    """
    M = metropolis_weights_jnp(jnp.asarray(W, jnp.float32))
    flat = values.reshape(values.shape[0], -1)
    M = M.astype(flat.dtype)

    def body(v, _):
        return mm(M, v), None

    out, _ = jax.lax.scan(body, flat, None, length=rounds)
    return out.reshape(values.shape)


def gossip_rounds_needed(W: np.ndarray, tol: float = 1e-6) -> int:
    """Rounds for worst-case contraction below tol: ceil(log tol / log s2)."""
    M = metropolis_weights(np.asarray(W)).astype(np.float64)
    eig = np.sort(np.abs(np.linalg.eigvals(M)))
    s2 = float(eig[-2]) if len(eig) > 1 else 0.0
    if s2 <= 0.0 or s2 >= 1.0:
        return 1 if s2 <= 0 else 10_000
    import math
    return int(math.ceil(math.log(tol) / math.log(s2)))


def decentralized_bic(X: Array, y: Array, B: Array, W: np.ndarray,
                      rounds: int = 60, tol: float = 1e-8
                      ) -> Tuple[Array, float]:
    """Modified BIC evaluated WITHOUT a fusion center.

    Each node contributes its local hinge total and support size; two gossip
    scalars propagate the averages; every node then forms the same BIC value
    (returned per-node, plus the exact centralized value for reference).
    """
    import math
    X, y, B = jnp.asarray(X), jnp.asarray(y), jnp.asarray(B)
    m, n, p = X.shape
    N = m * n
    margins = y * jnp.einsum("mnp,mp->mn", X, B, precision=F32)
    local_hinge = jnp.maximum(1.0 - margins, 0.0).sum(axis=1)      # (m,)
    local_supp = (jnp.abs(B) > tol).sum(axis=1).astype(jnp.float32)
    scalars = jnp.stack([local_hinge, local_supp], axis=1)          # (m, 2)
    avg = gossip_average(scalars, W, rounds)                        # (m, 2)
    hinge_term = avg[:, 0] * m / N        # avg*m = network sum
    supp_term = avg[:, 1]                 # mean support
    bic_per_node = hinge_term + math.sqrt(math.log(N)) * math.log(p - 1) \
        * supp_term / N
    exact = float(local_hinge.sum() / N + math.sqrt(math.log(N))
                  * math.log(p - 1) * local_supp.mean() / N)
    return bic_per_node, exact
